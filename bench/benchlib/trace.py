"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What a TPU trace holds: one plane per chip (``/device:TPU:<n>``) whose
line ``XLA Ops`` has an event per device operation, named by its HLO
text (a Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` and carries no kernel name),
and whose line ``XLA Modules`` has an event per run of a compiled
program (``jit_<name>(<fingerprint>)``); and the host plane
(``/host:CPU``) whose threads carry the benchmark's ``TraceAnnotation``
spans (``bench.*``) and the runtime's events.  The traced window is the
host span ``bench.window``, which ``TracedSlice`` opens and closes with
the profiler; device events are clipped to it.  Busy time is the union
of the operations' intervals, so nested or overlapping events count
once.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KERNEL_TAG = "[tpu_custom_call]"
CONTAINERS = ("while", "conditional")       # ops that enclose other ops


def find_xplane(log_dir) -> str:
    paths = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def profiler_options():
    """Host spans and runtime events, no Python function tracer (it would
    record every call of the serving loop) and no HLO protos."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


class TracedSlice:
    """The profiler over one slice of a run, which the driver starts and
    stops where the slice belongs (``start`` and ``stop`` at most once
    each); the slice is the host span ``bench.window``."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.started = self.stopped = False
        self._span = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.log_dir,
                                 profiler_options=profiler_options())
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.started = True

    def stop(self):
        if self.started and not self.stopped:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.stopped = True


# ----------------------------------------------------------------------
# interval arithmetic on (start, end) pairs in nanoseconds
def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float):
    """Idle intervals of [t0, t1] not covered by the (merged) ``busy``."""
    out, t = [], t0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < t1:
        out.append((t, t1))
    return out


@dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns


def short_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``; a Pallas
    kernel's op gets the tag ``[tpu_custom_call]``."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in hlo_text:
        name += " " + KERNEL_TAG
    return name


@dataclass
class Reduced:
    """A trace reduced to its window."""
    window_s: float
    busy_s: float                        # mean over the chips used
    ops: List[Event]                     # device operations of chip 0
    modules: List[Event]                 # program runs of chip 0
    idle: List[Tuple[float, float]]      # idle intervals of chip 0, ns
    host: List[Event]                    # host spans that label the gaps
    op_seconds_by_name: Dict[str, float] = field(default_factory=dict)

    def kernel_seconds(self, pattern: str = re.escape(KERNEL_TAG)) -> float:
        """Summed device time of operations whose name matches."""
        rx = re.compile(pattern)
        return sum(e.end - e.start for e in self.ops
                   if rx.search(e.name)) * 1e-9

    def kernel_count(self, pattern: str = re.escape(KERNEL_TAG)) -> int:
        rx = re.compile(pattern)
        return sum(1 for e in self.ops if rx.search(e.name))

    def program_runs(self, pattern: str) -> List[Event]:
        rx = re.compile(pattern)
        return [e for e in self.modules if rx.search(e.name)]

    def programs_holding(self, pattern: str = re.escape(KERNEL_TAG)
                         ) -> List[Event]:
        """Program runs during which an operation matching ``pattern``
        ran (the programs that call a kernel)."""
        rx = re.compile(pattern)
        marks = sorted((e.start + e.end) / 2 for e in self.ops
                       if rx.search(e.name))
        import bisect
        return [m for m in self.modules
                if bisect.bisect_left(marks, m.start)
                < bisect.bisect_right(marks, m.end)]

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_seconds_by_name.items(), key=lambda kv: -kv[1])
        longest = sorted(self.idle, key=lambda se: se[0] - se[1])[:n]
        return {"device_ops": [[k, v] for k, v in top[:n]],
                "idle_gaps": [[_label(self.host, (s + e) / 2), (e - s) * 1e-9]
                              for s, e in longest]}


def _label(host: List[Event], t: float) -> str:
    """The innermost host span that holds instant ``t``."""
    best = None
    for e in host:
        if e.start <= t <= e.end and (best is None or
                                      e.end - e.start < best.end - best.start):
            best = e
    return best.name if best is not None else "(no host span)"


def reduce_planes(planes: dict, n_devices: int,
                  spans: Tuple[str, str] = (WINDOW_SPAN, WINDOW_SPAN)) -> Reduced:
    """``planes``: {plane name: {line name: [Event]}} -> Reduced over the
    window from the start of span ``spans[0]`` to the end of ``spans[1]``.
    Idle gaps are labelled by the host thread that recorded the spans."""
    lines = [evs for name, ls in planes.items() if name.startswith("/host")
             for evs in ls.values()]
    first = [e for evs in lines for e in evs if e.name == spans[0]]
    last = [e for evs in lines for e in evs if e.name == spans[1]]
    if not first or not last:
        raise ValueError(f"no {spans} spans on the host plane")
    t0, t1 = first[0].start, last[-1].end
    devices = sorted((n for n in planes if n.startswith("/device:TPU:")),
                     key=lambda n: int(n.rsplit(":", 1)[1]))[:n_devices]
    if not devices:
        raise ValueError(f"no TPU device plane in {sorted(planes)}")
    busy_ns, ops0, mods0, idle = [], [], [], []
    for i, d in enumerate(devices):
        ops = [Event(e.name, s, x) for e in planes[d].get(OPS_LINE, [])
               for s, x in clip([(e.start, e.end)], t0, t1)]
        merged = union([(e.start, e.end) for e in ops])
        busy_ns.append(sum(e - s for s, e in merged))
        if i == 0:
            ops0, idle = ops, gaps(merged, t0, t1)
            # program runs that start in the window (the last one cut
            # at its end), so a run's start is its own
            mods0 = [Event(e.name, e.start, min(e.end, t1))
                     for e in planes[d].get(MODULES_LINE, [])
                     if t0 <= e.start < t1]
    by_name: Dict[str, float] = {}
    for e in ops0:
        if not e.name.startswith(CONTAINERS):
            by_name[e.name] = by_name.get(e.name, 0.0) \
                + (e.end - e.start) * 1e-9
    host = next(evs for evs in lines if any(e.name == spans[0] for e in evs))
    return Reduced(window_s=(t1 - t0) * 1e-9,
                   busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
                   ops=ops0, modules=mods0, idle=idle, host=host,
                   op_seconds_by_name=by_name)


def read_planes(path) -> dict:
    """The lines the reduction reads: the chips' operations and program
    runs, and every host thread; operation names shortened."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = {}
    for p in data.planes:
        if p.name.startswith("/device:TPU:"):
            planes[p.name] = {
                OPS_LINE: [Event(short_name(e.name), e.start_ns, e.end_ns)
                           for ln in p.lines if ln.name == OPS_LINE
                           for e in ln.events],
                MODULES_LINE: [Event(e.name, e.start_ns, e.end_ns)
                               for ln in p.lines if ln.name == MODULES_LINE
                               for e in ln.events]}
        elif p.name.startswith("/host"):
            planes[p.name] = {ln.name: [Event(e.name, e.start_ns, e.end_ns)
                                        for e in ln.events]
                              for ln in p.lines}
    return planes


def reduce(path, n_devices: int, spans=(WINDOW_SPAN, WINDOW_SPAN)) -> Reduced:
    return reduce_planes(read_planes(Path(path)), n_devices, spans)
