"""Kernels: the decode attention's least time (the larger of its needed
flops over the bf16 peak and its needed bytes over HBM bandwidth, from
``benchlib.counts.decode_attention_cost`` at each token's own context)
for the tokens that the traced slice's decode blocks decoded, over the
summed device time of the Pallas kernel's events (``tpu_custom_call``)
in the slice, in percent.  The needed bytes are q, the K/V of the
token's valid positions and the output, not the pool's full length, so
the count is the same whatever implements the attention."""
from benchlib import counts


def read(run):
    sl = run.window["traced"]
    t = run.trace.kernel_seconds()
    if not sl or not sl["contexts"] or t <= 0:
        return None
    flops, nbytes = counts.decode_attention_cost(run.cell.config,
                                                 sl["contexts"])
    least, _ = counts.roofline_seconds(flops, nbytes, run.peaks)
    return 100.0 * least / t
