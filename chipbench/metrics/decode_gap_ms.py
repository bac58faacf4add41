"""Device idle time between consecutive decode steps of one call, per
decode step: the host's work for each token (sampling, the fetch of the
token, the next dispatch), as the device sees it."""
from chipbench.metrics._programs import DECODE, PREFILL


def read(run):
    tr = run.trace
    if tr is None:
        return None
    idle, steps, prev = 0.0, 0, None
    for name, s, e in tr.programs(0):
        if name == DECODE:
            steps += 1
            if prev is not None:
                idle += (s - prev) - tr.busy_ns(0, prev, s)
            prev = e
        elif name == PREFILL:
            prev = None           # a new call: its first step has no gap
    return idle / steps / 1e6 if steps else None
