"""Least time of the decode steps run (``counts``: weights plus each
row's live KV context, against ``peaks``) over their device time."""
from chipbench.counts import min_seconds
from chipbench.metrics._programs import DECODE, durations_ns


def read(run):
    d = durations_ns(run, DECODE)
    # step j of a call attends over prompt + j + 1 positions
    ctx = [(c.batch, c.prompt_len + j + 1) for c in run.calls
           for j in range(c.n_new)]
    if not d or len(d) != len(ctx) or run.peaks is None:
        return None
    sh = run.shapes
    least = sum(min_seconds(sh.decode_flops(b, t), sh.decode_bytes(b, t),
                            run.peaks) for b, t in ctx)
    return 100.0 * least / (sum(d) / 1e9)
