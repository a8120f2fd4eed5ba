"""95th percentile over all requests of first token minus scheduled
arrival.  The first token counts when the host reads it at a block
boundary.  A request with no first token leaves the metric out."""
import numpy as np


def read(run):
    rows = run.window["requests"]
    if any(r["first_token_s"] is None for r in rows):
        return None
    return 1e3 * float(np.percentile(
        [r["first_token_s"] - r["arrival_s"] for r in rows], 95))
