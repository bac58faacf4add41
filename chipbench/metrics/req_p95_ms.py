"""Nearest-rank p95 of request latency over every request in the window;
a request's latency is that of the call that served it, from the
client's call to its return."""
from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(
        [c.latency_s for c in run.calls for _ in range(c.batch)], 0.95)
