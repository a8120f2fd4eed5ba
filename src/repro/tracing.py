"""Host spans on the device trace's clock, with wall and thread-CPU time.

``Span(name, **attrs)`` enters a ``jax.profiler.TraceAnnotation``: under
a profiler session the span lands in the ``.xplane.pb`` on the same
clock as the device's ``XLA Ops`` and ``XLA Modules`` lines, and
otherwise it is inert.  It also measures its wall time
(``time.perf_counter_ns``) and the calling thread's CPU time
(``time.thread_time_ns``), which the caller reads after the ``with``
block as ``wall_ns`` and ``cpu_ns``.  With the profiler off a span
costs its four clock reads: a few microseconds, more on a host where
reading the thread's CPU clock is a slow system call.
"""
from __future__ import annotations

import time

import jax


class Span:
    __slots__ = ("_ann", "_wall0", "_cpu0", "wall_ns", "cpu_ns")

    def __init__(self, name: str, **attrs):
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)
        self.wall_ns = self.cpu_ns = 0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        # the CPU reads enclose the wall reads, so that CPU time outside
        # the span never takes in a part of the span's wall time
        self._cpu0 = time.thread_time_ns()
        self._wall0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_ns = time.perf_counter_ns() - self._wall0
        self.cpu_ns = time.thread_time_ns() - self._cpu0
        self._ann.__exit__(*exc)
