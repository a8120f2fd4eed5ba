"""The serving engine's own per-block record of a run's window
(``stats["last_serve"]``, one record per decode block: its period from
dispatch to dispatch, the wall time of its host spans, and the serving
thread's CPU and GC time), less the blocks whose period overlaps the
traced slice: the profiler starts and stops inside their dispatches and
slows the host in between.  Records are matched by index to the
driver's dispatch times (``block_log``).  A program that keeps no such
record gives None."""
from __future__ import annotations


def outside_slice(run):
    """-> the records of the blocks outside the traced slice, or None."""
    log = (run.window.get("stats") or {}).get("last_serve")
    times = run.window.get("block_log", ([],))[0]
    if not log or not log["blocks"] or len(times) != log["totals"]["blocks"]:
        return None
    sl = run.window.get("traced")
    if not sl:
        return list(log["blocks"])
    ends = list(times[1:]) + [float("inf")]
    kept = [r for r in log["blocks"]
            if times[r.block] > sl["t1"] or ends[r.block] < sl["t0"]]
    return kept or None


def host_ns(r) -> int:
    """Host wall time of a block's period: all of it but the readback
    wait and the idle sleep."""
    return r.period_ns - r.wait_ns - r.idle_ns


def longest(run):
    """-> the record of the longest period outside the slice, idle sleep
    left out, or None."""
    blocks = outside_slice(run)
    if not blocks:
        return None
    return max(blocks, key=lambda r: r.period_ns - r.idle_ns)
