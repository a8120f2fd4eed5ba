"""Engine loop: of the host time of the longest block period outside
the traced slice (``serve.block_period_max_host_ms``), the part the
serving thread spent off the CPU (wall minus thread CPU time), in ms:
the host descheduled, not busy.  It is as fine as the thread's CPU
clock, which steps by 10 ms on some hosts: there a reading within 20 ms
of 0 means the thread was on the CPU."""
from benchlib import blocks


def read(run):
    r = blocks.longest(run)
    return None if r is None else 1e-6 * (blocks.host_ns(r) - r.cpu_ns)
