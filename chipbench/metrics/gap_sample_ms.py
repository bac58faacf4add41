"""Device idle between consecutive decode steps of one call while the
host was inside ``serve.sample`` (splitting the key, dispatching the
sampler) or in no step span of the engine, per decode step: the rest of
``decode_gap_ms``."""
from chipbench.metrics._serve_spans import gap_split


def read(run):
    split = gap_split(run)
    return None if split is None else split["sample"] + split["none"]
