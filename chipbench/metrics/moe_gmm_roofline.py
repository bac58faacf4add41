"""The ragged expert matmul's share of its roofline: its least time (the
architecture's ``gmm_flops`` and ``gmm_bytes`` for each MoE layer of
each prefill and decode step, against ``peaks``) over the self time of
its ops in the trace, in both programs. The kernel's ops are named
``moe_gmm`` (``repro.kernels.moe_gmm.NAME``, the Pallas call's name) in
the compiled programs; the experts each layer reads are the mean of the
program's routing counter. A run with no such ops, or no counter, reads
None."""
import re

from chipbench.counts import min_seconds
from chipbench.metrics._programs import DECODE, PREFILL
from chipbench.metrics._routing import mean_experts

KERNEL = re.compile(r"^(%s|%s)/moe_gmm(\.\d+)?$" % (DECODE, PREFILL))


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ns = sum(t for op, t in run.trace.op_self_ns.items() if KERNEL.match(op))
    dec, pre = mean_experts("decode"), mean_experts("prefill")
    if not ns or dec is None or pre is None:
        return None
    sh, layers = run.shapes, run.shapes.layers
    least = 0.0
    for c in run.calls:
        rows = c.batch * sh.top_k
        least += c.n_new * layers * min_seconds(
            sh.gmm_flops(rows), sh.gmm_bytes(rows, dec), run.peaks)
        rows *= c.prompt_len
        least += layers * min_seconds(
            sh.gmm_flops(rows), sh.gmm_bytes(rows, pre), run.peaks)
    return 100.0 * least / (ns / 1e9)
