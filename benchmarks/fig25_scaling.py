"""Paper Fig. 25: Neu10 throughput improvement over V10 as the core
grows (2ME/2VE .. 8ME/8VE, split evenly between the two vNPUs).
Paper claim: more MEs/VEs -> more benefit from μTOp scheduling.

Also carries the simulator perf micro-benchmarks:

* fast path — the largest sweep (8ME/8VE, the heaviest event load)
  re-runs with ``fast_path=False`` (the reference engine: unmemoized
  dispatch durations, engine-scan HBM pressure, the full neu10
  schedule pass every event) vs the default engine (the dirty-set
  scheduling core), asserting the SimResults are IDENTICAL and the
  wall-clock speedup is >= 1.3x (min-of-N timings to reject machine
  noise).
* fleet_sweep — the fig25 outer grid (pairs × EU splits × bandwidth
  points) as ONE jitted ``sweep_collocations`` XLA program vs the
  discrete simulator running the same grid one cell at a time; this
  is the row CI's benchmark-smoke asserts a >= 3x floor on (locally
  the single-dispatch sweep is >= 10x)."""
from __future__ import annotations

import time
from typing import List

from benchmarks.common import BenchRow, geomean, run_pair, timed
from repro.core.policies import resolve_policy
from repro.core.sim_jax import sweep_collocations
from repro.core.vnpu import VNPUConfig
from repro.npu.hw_config import NPUCoreConfig
from repro.npu.workloads import get_workload
from repro.serve.session import NPUCluster, run_closed_loop

PAIRS = [("ENet", "TFMR"), ("RNRS", "RtNt"), ("BERT", "ENet")]
SIZES = [2, 4, 8]

FAST_PATH_GAIN = 1.3   # required wall-clock speedup, largest sweep
FAST_PATH_REPS = 5     # min-of-N per variant (noise rejection)
FLEET_SWEEP_GAIN = 3.0   # jitted grid vs per-cell discrete (CI floor)

# fleet-sweep grid: pairs × EU splits × HBM-bandwidth points on the
# 8ME/8VE core (per-tenant (ME, VE) engine counts; asymmetric splits
# exercise the collocation-search question the sweep exists for)
SWEEP_SPLITS = (((4, 4), (4, 4)), ((6, 2), (6, 2)), ((2, 6), (2, 6)))
SWEEP_BW = (0.5, 1.0, 2.0)
SWEEP_REQUESTS = 4


def _fast_path_row() -> BenchRow:
    """Time the largest sweep (8ME/8VE BERT+ENet under neu10) on the
    reference vs the default engine; prove identical results."""
    core = NPUCoreConfig(n_me=8, n_ve=8)
    times = {True: [], False: []}
    results = {}
    for _ in range(FAST_PATH_REPS):
        for fast in (False, True):
            t0 = time.time()
            res = run_pair("BERT", "ENet", "neu10", core=core,
                           me_ve=(4, 4), fast_path=fast)
            times[fast].append(time.time() - t0)
            results[fast] = res
    ref, opt = results[False], results[True]
    identical = (ref.makespan == opt.makespan
                 and ref.tenants == opt.tenants)
    assert identical, "fast path diverged from the reference simulator"
    speedup = min(times[False]) / max(min(times[True]), 1e-9)
    assert speedup >= FAST_PATH_GAIN, (
        f"fast path {speedup:.2f}x < required {FAST_PATH_GAIN}x")
    return BenchRow(
        "fig25/fast_path/BERT+ENet/8ME8VE",
        min(times[True]) * 1e6,
        f"speedup={speedup:.2f}x identical=True "
        f"ref_us={min(times[False]) * 1e6:.0f}")


def _fleet_sweep_row() -> BenchRow:
    """The fig25 outer grid (pair × EU split × HBM bandwidth) two
    ways: the discrete simulator one cell at a time vs ONE jitted
    ``sweep_collocations`` dispatch covering the whole lattice. The
    sweep call is warmed once (XLA compile amortizes over every later
    grid at these shapes) and timed min-of-N; the discrete grid runs
    each cell as a fresh event loop, exactly how the figures drive
    it. Both sides answer the same capacity-planning query — the
    discrete engine stays the validated oracle (the fluid model's
    ordering is pinned against it in tests/test_sim_jax.py)."""
    core = NPUCoreConfig(n_me=8, n_ve=8)
    pol = resolve_policy("neu10")
    progs = {n: pol.compile_program(get_workload(n, core), core)
             for n in {w for pair in PAIRS for w in pair}}
    prog_pairs = [(progs[a], progs[b]) for a, b in PAIRS]

    def discrete_cell(pair, split, bw):
        (m1, m2), (v1, v2) = split
        cluster = NPUCluster(core=core, policy="neu10")
        for name, m, v in ((pair[0], m1, v1), (pair[1], m2, v2)):
            cluster.register_vnpu(
                name, get_workload(name, core),
                VNPUConfig(n_me=m, n_ve=v,
                           hbm_bytes=core.hbm_bytes // 2,
                           sram_bytes=core.sram_bytes // 2))
        res, _ = run_closed_loop(cluster, n_requests=SWEEP_REQUESTS,
                                 hbm_scale=bw)
        return res

    t0 = time.time()
    for pair in PAIRS:
        for split in SWEEP_SPLITS:
            for bw in SWEEP_BW:
                discrete_cell(pair, split, bw)
    discrete_wall = time.time() - t0

    def sweep():
        out = sweep_collocations(prog_pairs, SWEEP_SPLITS,
                                 bw_points=SWEEP_BW,
                                 n_requests=SWEEP_REQUESTS, core=core)
        out["makespan"].block_until_ready()
        return out

    sweep()   # warm-up: XLA compilation paid once
    sweep_wall = 1e9
    for _ in range(3):
        t0 = time.time()
        out = sweep()
        sweep_wall = min(sweep_wall, time.time() - t0)
    n_cells = len(PAIRS) * len(SWEEP_SPLITS) * len(SWEEP_BW)
    assert out["makespan"].shape == (len(PAIRS), len(SWEEP_SPLITS),
                                     len(SWEEP_BW))
    speedup = discrete_wall / max(sweep_wall, 1e-9)
    assert speedup >= FLEET_SWEEP_GAIN, (
        f"vectorized fleet sweep {speedup:.1f}x < required "
        f"{FLEET_SWEEP_GAIN}x over the per-cell discrete grid")
    return BenchRow(
        "fig25/fleet_sweep/grid",
        sweep_wall * 1e6,
        f"speedup={speedup:.1f}x cells={n_cells} "
        f"discrete_us={discrete_wall * 1e6:.0f}")


def run() -> List[BenchRow]:
    rows: List[BenchRow] = []
    gains_by_size = {}
    for n in SIZES:
        core = NPUCoreConfig(n_me=n, n_ve=n)
        half = (n // 2, n // 2)
        gains = []
        for w1, w2 in PAIRS:
            us, pair = timed(lambda a=w1, b=w2: (
                run_pair(a, b, "neu10", core=core, me_ve=half),
                run_pair(a, b, "v10", core=core, me_ve=half)))
            neu, v10 = pair
            g = neu.total_throughput() / max(v10.total_throughput(), 1e-9)
            gains.append(g)
            rows.append(BenchRow(
                f"fig25/{w1}+{w2}/{n}ME{n}VE", us, f"neu10/v10={g:.3f}"))
        gains_by_size[n] = geomean(gains)
        rows.append(BenchRow(f"fig25/geomean/{n}ME{n}VE", 0.0,
                             f"{gains_by_size[n]:.3f}"))
    # scaling trend: benefit at 8 engines >= benefit at 2 engines
    assert gains_by_size[8] >= gains_by_size[2] - 0.05
    rows.append(_fast_path_row())
    rows.append(_fleet_sweep_row())
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
