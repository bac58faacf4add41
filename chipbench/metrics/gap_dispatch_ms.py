"""Device idle between consecutive decode steps of one call while the
host was inside ``serve.dispatch`` (putting the cache index on the
device and launching the decode step), per decode step: a part of
``decode_gap_ms``."""
from chipbench.metrics._serve_spans import gap_split


def read(run):
    split = gap_split(run)
    return None if split is None else split["dispatch"]
