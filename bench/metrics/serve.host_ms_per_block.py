"""Engine loop: the host's own work per decode block outside the traced
slice, from the engine's spans: ``serve.admit``, ``serve.block.dispatch``
and ``serve.block.bookkeep`` wall time summed over those blocks, over
their number, in ms.  A mean, not a median: about half the blocks admit
nothing, and an admission costs the host about 5 ms, so a median jumps
between about 1.5 and 6.5 ms from seed to seed."""
from benchlib import blocks


def read(run):
    kept = blocks.outside_slice(run)
    if not kept:
        return None
    return 1e-6 * sum(r.admit_ns + r.dispatch_ns + r.bookkeep_ns
                      for r in kept) / len(kept)
