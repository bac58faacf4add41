#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, many seeds in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3

For each seed: weights and traffic from the seed, one round of the
cell's calls through the system (shapes warmed once, before the first
seed), and the check that ``run.py`` makes on it: the widest logit gap
of the served tokens under the float32 reference, and the same reading
for the reference computed in fp8 in the program's place (the control).
One JSON line per seed, with the verdict of ``check.verdict`` under the
cell's limits for each: ``correct`` for the program, ``control_correct``
for the control put in its place. It needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time

from run import ROOT, setup_jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    jax = setup_jax()
    from chipbench import check
    from chipbench.spec import load_cell
    from chipbench.weights import make_weights

    cell = load_cell(ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cfg, traffic = cell.config, cell.traffic
    drv_mod = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    system = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        weights = make_weights(cfg, seed, cfg["serve_dtype"])
        driver = drv_mod.Driver(traffic, cfg["vocab_size"], seed)
        if system is None:
            system = importlib.import_module(
                f"chipbench.adapters.{cfg['adapter']}").System(
                    cfg, traffic, weights)
            driver.warm_up(system.generate)
        else:
            system.set_weights(weights)
        calls = driver.run(system.generate, 0.0, max_rounds=1)
        drv_mod.settle()
        picks = check.sample(calls, int(traffic["check_requests"]), seed)
        got = check.compare(cfg, weights, calls, picks,
                            int(traffic["max_seq"]), control=True)
        correct, _ = check.verdict(
            got, cell.limits, check.failed_requests(calls, cfg["vocab_size"]))
        control_correct, _ = check.verdict(
            {"logit_gap": got["control_logit_gap"]}, cell.limits, 0)
        print(json.dumps({"workload": cell.name, "seed": seed, **got,
                          "correct": correct,
                          "control_correct": control_correct,
                          "seconds": time.perf_counter() - t0}), flush=True)
        system.set_weights(None)
        del weights
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
