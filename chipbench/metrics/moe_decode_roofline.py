"""Least time of the MoE decode steps run (the architecture's counts:
every weight but the routed experts', the experts routed to, read once,
and each row's live KV context, against ``peaks``) over their device
time. The experts a step reads are the mean per layer of the program's
routing counter (``_routing``)."""
from chipbench.counts import min_seconds
from chipbench.metrics._programs import DECODE, durations_ns
from chipbench.metrics._routing import mean_experts


def read(run):
    d = durations_ns(run, DECODE)
    experts = mean_experts("decode")
    # step j of a call attends over prompt + j + 1 positions
    ctx = [(c.batch, c.prompt_len + j + 1) for c in run.calls
           for j in range(c.n_new)]
    if not d or len(d) != len(ctx) or run.peaks is None or experts is None:
        return None
    sh = run.shapes
    least = sum(min_seconds(sh.decode_flops(b, t),
                            sh.decode_bytes(b, t, experts), run.peaks)
                for b, t in ctx)
    return 100.0 * least / (sum(d) / 1e9)
