"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig19_21   # one figure

``--json [PATH]`` additionally writes the rows as structured records
(default ``BENCH_serving.json``) so the perf trajectory is
machine-readable: each record carries the suite, row name,
``us_per_call``, the raw derived string AND a ``metrics`` dict parsed
from its ``key=value`` pairs (numeric values with their unit suffixes
stripped). The top-level ``wall_s`` map records each suite's total
wall-clock seconds, so suite-level runtime regressions are tracked
alongside the per-row numbers. CI's benchmark-smoke job uploads the
file as an artifact.
"""
from __future__ import annotations

import argparse
import json
import re
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional

from benchmarks import (
    fig12_allocator,
    fig16_neuisa_overhead,
    fig19_21_latency_throughput,
    fig22_utilization,
    fig25_scaling,
    fig26_hbm,
    fig_admission,
    fig_chunked_prefill,
    fig_colocation,
    fig_fabric,
    fig_fault,
    fig_kv_pressure,
    fig_prefix_cache,
    table3_harvest_overhead,
)
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "fig12": fig12_allocator,
    "fig16": fig16_neuisa_overhead,
    "fig19_21": fig19_21_latency_throughput,
    "fig22": fig22_utilization,
    "table3": table3_harvest_overhead,
    "fig25": fig25_scaling,
    "fig26": fig26_hbm,
    "fig_colocation": fig_colocation,
    "fig_chunked_prefill": fig_chunked_prefill,
    "fig_fabric": fig_fabric,
    "fig_fault": fig_fault,
    "fig_kv_pressure": fig_kv_pressure,
    "fig_prefix_cache": fig_prefix_cache,
    "fig_admission": fig_admission,
}

# "chat_ttft_p95=0.0063ms" / "speedup=1.50x" / "interleaved=9" ->
# numeric value with the unit suffix stripped; non-numeric values
# (e.g. "identical=True") are kept as strings
_NUM = re.compile(r"^(-?\d+(?:\.\d+)?(?:e-?\d+)?)([a-zA-Z%/]*)$")


def parse_metrics(derived: str) -> Dict[str, object]:
    """Parse a row's ``key=value`` derived string into a dict (other
    tokens are ignored); numbers lose their unit suffix."""
    out: Dict[str, object] = {}
    for tok in derived.split():
        if "=" not in tok:
            continue
        key, _, val = tok.partition("=")
        m = _NUM.match(val)
        out[key] = float(m.group(1)) if m else val
    return out


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="run paper-figure benchmark suites")
    ap.add_argument("suites", nargs="*", metavar="suite", default=[],
                    help=f"suites to run (default: all): {', '.join(SUITES)}")
    ap.add_argument("--json", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="also write rows as JSON records "
                         "(default path: BENCH_serving.json)")
    args = ap.parse_args(argv)
    unknown = [s for s in args.suites if s not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; known: {', '.join(SUITES)}")
    use_compile_cache(Path(__file__).resolve().parents[1])
    selected = args.suites or list(SUITES)
    print("name,us_per_call,derived")
    failures = []
    records = []
    wall_s: Dict[str, float] = {}
    for key in selected:
        mod = SUITES[key]
        t0 = time.time()
        try:
            for row in mod.run():
                print(row.csv(), flush=True)
                records.append({
                    "suite": key,
                    "name": row.name,
                    "us_per_call": round(row.us_per_call, 1),
                    "derived": row.derived,
                    "metrics": parse_metrics(row.derived),
                })
            wall_s[key] = round(time.time() - t0, 3)
            print(f"{key}/TOTAL,{(time.time()-t0)*1e6:.0f},ok", flush=True)
        except Exception as e:
            traceback.print_exc()
            wall_s[key] = round(time.time() - t0, 3)
            print(f"{key}/TOTAL,0,FAILED: {e}", flush=True)
            failures.append(key)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "suites": selected,
                       "failures": failures, "wall_s": wall_s,
                       "rows": records},
                      f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(records)} records to {args.json}", flush=True)
    if failures:
        raise SystemExit(f"benchmark suites failed: {failures}")


if __name__ == "__main__":
    main()
