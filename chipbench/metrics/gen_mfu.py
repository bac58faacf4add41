"""Operations that the traced calls needed (``counts``: each call's
prefill and one decode step per further token) over the traced window's
time times the chip's bf16 peak."""
from chipbench.counts import call_useful_flops


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.trace.window()
    flops = sum(call_useful_flops(run.shapes, c.batch, c.prompt_len, c.n_new)
                for c in run.calls)
    return 100.0 * flops / ((hi - lo) / 1e9 * run.peaks["bf16_flops_per_s"])
