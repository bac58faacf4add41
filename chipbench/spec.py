"""Find a cell of BENCHMARK.json and everything it names, by name.

* the configuration: the file its ``configs`` entry names;
* the traffic mix: ``chipbench/traffic/<traffic>.json``;
* the cell's limits on what the check compares:
  ``chipbench/cells/<workload>.json``;
* each metric's reader: ``chipbench/metrics/<metric>.py``.

A metric belongs to a cell when its ``workloads`` list names the cell;
without the list, an end-to-end metric belongs to every cell and a
per-layer one to every cell that reports the metric it ``moves``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Mapping

PKG = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _belongs(metric: Mapping[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _belongs(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((PKG / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        limits=json.loads((PKG / "cells" / f"{name}.json").read_text())
        ["limits"],
        end_to_end=e2e, per_layer=layer)
