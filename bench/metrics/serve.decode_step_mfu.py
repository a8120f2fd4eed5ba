"""Model decode step: required flops of the tokens that the traced
slice's decode blocks decoded (``benchlib.counts.decode_token_flops``,
each at its own context) over the device time of the programs that run
the decode attention kernel (the fused decode blocks) times the chip's
bf16 peak, in percent."""
from benchlib import counts


def read(run):
    sl = run.window["traced"]
    t = sum(e.end - e.start for e in run.trace.programs_holding()) * 1e-9
    if not sl or not sl["contexts"] or t <= 0:
        return None
    flops = sum(counts.decode_token_flops(run.cell.config, c)
                for c in sl["contexts"])
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
