"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
leads to the files the harness looks up."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    from chipbench.spec import load_cell

    for w in BENCH["workloads"]:
        cell = load_cell(ROOT, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert all(m["moves"] in names for m in cell.per_layer)


def test_every_name_has_its_files():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("chipbench/configs/")
        assert cfg["source"] == c["source"] + "/blob/main/config.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (ROOT / "chipbench" / "adapters"
                / f"{cfg['adapter']}.py").exists()
        assert (ROOT / "chipbench" / "references"
                / f"{cfg['reference']}.py").exists()
    for w in BENCH["workloads"]:
        tr = json.loads((ROOT / "chipbench" / "traffic"
                         / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chipbench" / "drivers" / f"{tr['driver']}.py").exists()
        assert (ROOT / "chipbench" / "cells" / f"{w['name']}.json").exists()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()


def test_layers_are_named_as_perf_md_lists_them():
    perf = (ROOT / "PERF.md").read_text()
    for m in BENCH["per_layer"]:
        assert f"**{m['layer']}**" in perf


@pytest.mark.parametrize("cells", [24])
def test_run_seconds_fit_a_full_check(cells):
    s = BENCH["run_seconds"]
    assert 1 <= s <= 51
    runs = 2 + 14 * cells
    assert runs * (s + 60) + cells * 2 * 90 + 1200 <= 43200
