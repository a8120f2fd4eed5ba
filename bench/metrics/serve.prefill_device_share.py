"""Model prefill step: device time of the admission program
(``jit_serve_admit``: prefill, first token, slot scatter) over that of
the admissions and the decode blocks (``jit_serve_decode_block``) that
start in the traced slice, in percent.  A program whose decode block
carries no such name gives None."""

NAME = r"^jit_{}(\(|$)"


def read(run):
    block = run.trace.program_runs(NAME.format("serve_decode_block"))
    if not block:
        return None
    admit = run.trace.program_runs(NAME.format("serve_admit"))
    t_admit = sum(e.end - e.start for e in admit)
    t_block = sum(e.end - e.start for e in block)
    return 100.0 * t_admit / (t_admit + t_block)
