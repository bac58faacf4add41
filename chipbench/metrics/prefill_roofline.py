"""Least time of the prefills run (``counts``: causal attention, logits
at the last position only, against ``peaks``) over their device time."""
from chipbench.counts import min_seconds
from chipbench.metrics._programs import PREFILL, durations_ns


def read(run):
    d = durations_ns(run, PREFILL)
    if not d or len(d) != len(run.calls) or run.peaks is None:
        return None
    sh = run.shapes
    least = sum(min_seconds(sh.prefill_flops(c.batch, c.prompt_len),
                            sh.prefill_bytes(c.batch, c.prompt_len),
                            run.peaks) for c in run.calls)
    return 100.0 * least / (sum(d) / 1e9)
