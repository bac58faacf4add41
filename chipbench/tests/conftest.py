"""Cells at test widths, run on the CPU through the benchmark's own code."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def smoke_cell():
    from chipbench.spec import Cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return Cell(name="smoke", chips=1,
                config=json.loads((DATA / "smoke-config.json").read_text()),
                traffic=json.loads((DATA / "smoke-traffic.json").read_text()),
                limits={"logit_gap": 0.05},
                end_to_end=bench["end_to_end"],
                per_layer=bench["per_layer"])
