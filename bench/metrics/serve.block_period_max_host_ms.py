"""Engine loop: of the longest block period outside the traced slice
(``serve.block_period_max_ms``), the host's wall time outside the
readback wait (``serve.block.wait``) and the idle sleep, in ms.  The
rest of the period is the device or the runtime."""
from benchlib import blocks


def read(run):
    r = blocks.longest(run)
    return None if r is None else 1e-6 * blocks.host_ns(r)
