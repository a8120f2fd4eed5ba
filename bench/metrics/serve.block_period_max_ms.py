"""Engine loop: the longest period from one decode block's dispatch to
the next outside the traced slice, less the engine's idle sleep, in
ms."""
from benchlib import blocks


def read(run):
    r = blocks.longest(run)
    return None if r is None else 1e-6 * (r.period_ns - r.idle_ns)
