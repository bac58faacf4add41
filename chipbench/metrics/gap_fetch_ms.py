"""Device idle between consecutive decode steps of one call while the
host was inside ``serve.fetch`` (waiting for the step's token), per
decode step: the part of ``decode_gap_ms`` spent waiting on the token."""
from chipbench.metrics._serve_spans import gap_split


def read(run):
    split = gap_split(run)
    return None if split is None else split["fetch"]
