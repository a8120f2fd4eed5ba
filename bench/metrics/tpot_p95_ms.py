"""95th percentile over requests of the time per output token after the
first: (finished - first token) / (tokens - 1).  Robust to the decode
block's chunking, which a gap between readbacks is not.  A request that
did not complete leaves the metric out."""
import numpy as np


def read(run):
    rows = run.window["requests"]
    if not all(r["completed"] for r in rows):
        return None
    return 1e3 * float(np.percentile(
        [(r["finished_s"] - r["first_token_s"]) / (len(r["tokens"]) - 1)
         for r in rows if len(r["tokens"]) > 1], 95))
