"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import VNPUConfig, available_policies
from repro.core.policies import PolicyLike
from repro.core.simulator import SimResult
from repro.npu.hw_config import DEFAULT_CORE, NPUCoreConfig
from repro.npu.workloads import PAPER_PAIRS, get_workload
from repro.serve.session import NPUCluster, run_closed_loop

# the paper's four disciplines, in §V-A presentation order (all
# resolved through the scheduler registry; extra registered policies
# can be swept by passing an explicit `policies` list to the figures)
POLICIES = ("pmt", "v10", "neu10_nh", "neu10")
assert all(p in available_policies() for p in POLICIES)


def run_pair(
    w1: str,
    w2: str,
    policy: PolicyLike,
    core: NPUCoreConfig = DEFAULT_CORE,
    n_requests: int = 6,
    hbm_scale: float = 1.0,
    me_ve: Tuple[int, int] = (2, 2),
    fast_path: bool = True,
) -> SimResult:
    """Paper §V-A setup: two vNPUs of 2ME/2VE on a 4ME/4VE core,
    SRAM/HBM split evenly. The policy (any registry entry) picks the
    mapping scheme and compiler front-end — temporal baselines compile
    whole VLIW operators for the full physical core; the false
    contention (Fig. 9) comes from operators whose own tiling can't
    fill it (n_tiles < n_me)."""
    cluster = NPUCluster(core=core, policy=policy)
    for name in (w1, w2):
        cluster.register_vnpu(
            name, get_workload(name, core),
            VNPUConfig(*me_ve, hbm_bytes=core.hbm_bytes // 2,
                       sram_bytes=core.sram_bytes // 2))
    res, _ = run_closed_loop(cluster, n_requests=n_requests,
                             hbm_scale=hbm_scale, fast_path=fast_path)
    return res


def geomean(xs) -> float:
    xs = [max(x, 1e-12) for x in xs]
    return float(np.exp(np.mean(np.log(xs))))


@dataclass
class BenchRow:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def timed(fn) -> Tuple[float, object]:
    t0 = time.time()
    out = fn()
    return (time.time() - t0) * 1e6, out
