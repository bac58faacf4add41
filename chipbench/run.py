#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One process: it makes the weights and inputs from the seed, builds the
system, warms up every shape the cell's traffic uses (set-up), then
measures whole rounds of calls for at least ``--seconds`` (``--trace 0``)
or traces one round (``--trace 1``), checks a sample of what was served
against the configuration's float32 reference, and prints one JSON line
last on standard output: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``checks`` (each number compared beside its limit, also printed as the
last lines of standard error).

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits 2 and prints no result. JAX's compilation cache is
kept in ``.chipbench_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / ".chipbench_cache"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Import JAX with the compile cache inside the checkout."""
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv=None) -> int:
    args = parse(argv)
    jax = setup_jax()
    from chipbench.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    t_import = time.perf_counter()
    devs = jax.devices()
    print(f"start (s): imports {t_import - T0:.3f}, devices "
          f"{time.perf_counter() - t_import:.3f}", file=sys.stderr)
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX finds {len(devs)}",
              file=sys.stderr)
        return 2

    from chipbench import counts, harness

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T0, CACHE / "trace",
                              counts.peaks_for(devs[0].device_kind))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
