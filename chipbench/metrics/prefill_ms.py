"""Device time per run of the prefill program."""
from chipbench.metrics._programs import PREFILL, durations_ns


def read(run):
    d = durations_ns(run, PREFILL)
    return sum(d) / len(d) / 1e6 if d else None
