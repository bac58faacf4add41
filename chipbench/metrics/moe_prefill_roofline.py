"""Least time of the MoE prefills run (the architecture's counts: causal
attention, logits at the last position only, the experts routed to read
once, against ``peaks``) over their device time. The experts a prefill
reads are the mean per layer of the program's routing counter."""
from chipbench.counts import min_seconds
from chipbench.metrics._programs import PREFILL, durations_ns
from chipbench.metrics._routing import mean_experts


def read(run):
    d = durations_ns(run, PREFILL)
    experts = mean_experts("prefill")
    if (not d or len(d) != len(run.calls) or run.peaks is None
            or experts is None):
        return None
    sh = run.shapes
    least = sum(min_seconds(sh.prefill_flops(c.batch, c.prompt_len),
                            sh.prefill_bytes(c.batch, c.prompt_len, experts),
                            run.peaks) for c in run.calls)
    return 100.0 * least / (sum(d) / 1e9)
