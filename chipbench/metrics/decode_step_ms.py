"""Device time per run of the decode-step program."""
from chipbench.metrics._programs import DECODE, durations_ns


def read(run):
    d = durations_ns(run, DECODE)
    return sum(d) / len(d) / 1e6 if d else None
