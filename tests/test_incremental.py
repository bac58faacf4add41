"""The default engine (the incremental dirty-set scheduling core):
result identity against the reference engine, ``fast_path=False``
(per policy, on the cohort-heavy config, under an overridden
``dispatch``, under KV pressure, and under randomized mid-run churn),
the session's engine selection, event-heap hygiene (lazy deletion
stays bounded; compaction is result-invariant) and the cluster event
heap's idle-core migration wake-up."""
import math

import pytest

from hypothesis_compat import given, settings, st
from repro.configs.qwen2_0_5b import SMOKE as CHAT
from repro.core.compiler import compile_neuisa, compile_vliw
from repro.core.fabric import FabricTopology, Placement
from repro.core.mapper import ReconfigureError, VNPUManager
from repro.core.simulator import Simulator, TenantSpec
from repro.core.vnpu import VNPUConfig
from repro.npu.hw_config import DEFAULT_CORE, NPUCoreConfig
from repro.npu.workloads import get_workload
from repro.serve.session import (GenLenDistribution, NPUCluster,
                                 PoissonArrivals, ServingSession)

POLICIES = ("pmt", "v10", "neu10_nh", "neu10")

SEG = 64 * 1024
KV_CORE = DEFAULT_CORE.with_(hbm_bytes=1024 * SEG, hbm_segment=SEG)


def _pair_specs(policy, core, me_ve=(2, 2), n_requests=4,
                names=("BERT", "ENet")):
    mgr = VNPUManager(core=core)
    spatial = policy.startswith("neu10")
    specs = []
    for name in names:
        v = mgr.create(VNPUConfig(*me_ve, hbm_bytes=1 << 30),
                       mapping="spatial" if spatial else "temporal")
        tr = get_workload(name, core)
        prog = (compile_neuisa(tr, core) if spatial
                else compile_vliw(tr, core))
        specs.append(TenantSpec(prog, v, n_requests))
    return specs


def _two_way(specs, policy, core):
    """ref (fast_path off) / inc (the default, dirty-set core)."""
    ref = Simulator(specs, policy=policy, core=core,
                    fast_path=False).run()
    inc = Simulator(specs, policy=policy, core=core).run()
    return ref, inc


# ----------------------------------------------------------------------
# bit-identity pins (the goldens every earlier PR validated stay exact)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("policy", POLICIES)
def test_incremental_identical_per_policy(policy):
    """Each registry policy — ported (neu10) or falling back to its
    full schedule pass — produces the SAME SimResult through the
    incremental dispatch core."""
    core = DEFAULT_CORE
    specs = _pair_specs(policy, core)
    ref, inc = _two_way(specs, policy, core)
    assert inc == ref


COHORT_CORE = NPUCoreConfig(n_me=8, n_ve=8)


def _cohort_specs(policy):
    return _pair_specs(policy, COHORT_CORE, me_ve=(4, 4), n_requests=6)


@pytest.mark.parametrize("policy", ["neu10", "neu10_nh"])
def test_incremental_identical_on_cohort_config(policy):
    """The 8ME/8VE half-split sweep is the cohort-heavy load (runs of
    identical non-contender chunks dispatched under one completion
    event) — exactness there pins the batched completion path."""
    ref, inc = _two_way(_cohort_specs(policy), policy, COHORT_CORE)
    assert inc == ref


@pytest.mark.parametrize("policy", ["neu10", "neu10_nh"])
def test_overridden_dispatch_sees_every_chunk(policy):
    """An instance-patched ``dispatch`` (a spy) sends the default
    engine's schedule pass through the reference pass, so the spy
    sees chunks of every tenant — and the SimResult is the one the
    unpatched engine gives."""
    specs = _cohort_specs(policy)
    plain = Simulator(specs, policy=policy, core=COHORT_CORE).run()
    sim = Simulator(specs, policy=policy, core=COHORT_CORE)
    seen = set()
    orig = sim.dispatch

    def spy(chunk, engines, t, harvested=False):
        seen.add(chunk.tenant)
        return orig(chunk, engines, t, harvested)

    sim.dispatch = spy
    assert sim.run() == plain
    assert seen == {0, 1}


def test_session_fast_path_selects_engine_on_every_core():
    """``ServingSession(fast_path=False)`` builds every core's
    simulator on the reference engine; the default builds them all
    on the incremental core."""
    def sims(**kw):
        return ServingSession(
            NPUCluster(core=KV_CORE, policy="neu10",
                       topology=FabricTopology.mesh(4)), **kw).sims

    ref, inc = sims(fast_path=False), sims()
    assert len(ref) == len(inc) == 4
    assert all(not s.fast_path and not s._inc for s in ref)
    assert all(s.fast_path and s._inc for s in inc)


_WSEG = -(-(CHAT.param_count() * 2) // SEG) * SEG   # weights, rounded


def _kv_pressure_result(fast_path):
    """Decode-heavy open-loop burst on a weights + 2-segment HBM pin:
    concurrent decodes overflow the ledger, forcing evict/swap-resume
    round trips (the fig_kv_pressure mix)."""
    sess = ServingSession(NPUCluster(core=KV_CORE, policy="neu10"),
                          fast_path=fast_path)
    chat = sess.register_generative(
        "chat", CHAT, prompt_len=128,
        gen_lens=GenLenDistribution(mean=96.0, max_len=256, seed=11),
        eu_budget=4, kv_policy="evict", hbm_bytes=_WSEG + 2 * SEG)
    sess.submit_arrivals(chat, PoissonArrivals(rate_rps=200_000.0,
                                               n=24, seed=1))
    sess.drain()
    return sess.sims[0].result()


def test_incremental_identical_under_kv_pressure():
    """Live KV accounting (evictions, swap-in resumes) drives the
    simulator down its pressure paths; the incremental core must
    reproduce the ledger trajectory exactly."""
    inc = _kv_pressure_result(True)
    ref = _kv_pressure_result(False)
    assert inc == ref
    assert any(t.kv_evictions > 0 and t.kv_swapins > 0
               for t in inc.tenants)


# ----------------------------------------------------------------------
# randomized mid-run churn (arrivals / adds / removes / resizes)
# ----------------------------------------------------------------------
_OP = st.one_of(
    st.tuples(st.just("run"), st.integers(1, 40)),
    st.tuples(st.just("arrive"), st.integers(0, 5), st.integers(1, 3)),
    st.tuples(st.just("add"), st.integers(0, 1)),
    st.tuples(st.just("remove"), st.integers(0, 5)),
    st.tuples(st.just("resize"), st.integers(0, 5), st.integers(2, 6)),
)

_CHURN_CORE = NPUCoreConfig(n_me=4, n_ve=4)
_CHURN_TRACES = ("DLRM", "ENet")


def _churn_result(ops, fast_path):
    """Replay one churn script on a fresh open-loop simulator. Every
    side effect is keyed to an ABSOLUTE time cursor (never sim.now),
    so the script is replayable independent of engine internals."""
    cluster = NPUCluster(core=_CHURN_CORE, policy="neu10")
    sim = Simulator((), policy="neu10", core=_CHURN_CORE,
                    fast_path=fast_path)
    active = []          # (handle, sim_idx)
    t = 0.0
    serial = 0
    for op in ops:
        kind = op[0]
        if kind == "run":
            t += op[1] * 4_000.0
            sim.run_until(t)
        elif kind == "add" and len(active) < 3:
            name = f"t{serial}"
            serial += 1
            try:
                h = cluster.register_vnpu(
                    name, get_workload(_CHURN_TRACES[op[1]], _CHURN_CORE),
                    VNPUConfig(1, 1, hbm_bytes=64 << 20,
                               sram_bytes=1 << 20))
            except RuntimeError:
                continue     # no free engines right now
            idx = sim.add_tenant(
                TenantSpec(cluster.compile(h.trace), h.vnpu),
                open_loop=True)
            active.append((h, idx))
        elif kind == "arrive" and active:
            _, idx = active[op[1] % len(active)]
            for i in range(op[2]):
                sim.inject_request(idx, t + i * 500.0)
        elif kind == "remove" and active:
            h, idx = active.pop(op[1] % len(active))
            sim.remove_tenant(idx)
            cluster.deregister(h)
        elif kind == "resize" and active:
            h, idx = active[op[1] % len(active)]
            try:
                cluster.resize(h, op[2])
            except (ReconfigureError, RuntimeError):
                continue
            sim.update_tenant_vnpu(idx, h.vnpu)
    sim.run_until(math.inf)
    return sim.result()


@settings(max_examples=12, deadline=None)
@given(ops=st.lists(_OP, min_size=4, max_size=14))
def test_property_churn_incremental_identity(ops):
    """Under arbitrary interleavings of arrivals, tenant adds/removes
    and live resizes, the incremental core's SimResult is bit-
    identical to the reference engine."""
    assert _churn_result(ops, True) == _churn_result(ops, False)


# ----------------------------------------------------------------------
# event-heap hygiene
# ----------------------------------------------------------------------
def _heap_churn(compact_min=None):
    """Sustained cancellation churn: tenant A serves a rolling open
    loop while short-lived BERT tenants (long per-chunk durations, so
    their pending completion events sit FAR in the future) keep
    registering and deregistering — each removal lazily cancels its
    in-flight events. Returns the peak event-heap size observed."""
    cluster = NPUCluster(core=_CHURN_CORE, policy="neu10")
    sim = Simulator((), policy="neu10", core=_CHURN_CORE)
    if compact_min is not None:
        sim.HEAP_COMPACT_MIN = compact_min
    a = cluster.register_vnpu(
        "a", get_workload("DLRM", _CHURN_CORE),
        VNPUConfig(2, 2, hbm_bytes=64 << 20, sram_bytes=1 << 20))
    ia = sim.add_tenant(TenantSpec(cluster.compile(a.trace), a.vnpu),
                        open_loop=True)
    t = 0.0
    peak = 0
    for i in range(120):
        sim.inject_request(ia, t)
        b = cluster.register_vnpu(
            f"b{i}", get_workload("BERT", _CHURN_CORE),
            VNPUConfig(2, 2, hbm_bytes=64 << 20, sram_bytes=1 << 20))
        ib = sim.add_tenant(TenantSpec(cluster.compile(b.trace), b.vnpu),
                            open_loop=True)
        for j in range(2):
            sim.inject_request(ib, t + j * 100.0)
        t += 500.0
        sim.run_until(t)
        sim.remove_tenant(ib)
        cluster.deregister(b)
        peak = max(peak, len(sim._heap))
    sim.run_until(math.inf)
    return peak


def test_heap_bounded_under_cancellation_churn():
    """Lazy deletion + threshold compaction keep the event heap
    bounded under sustained cancellation churn; with compaction
    disabled the SAME script accumulates strictly more stale entries
    (the bloat the compaction ledger exists to prune)."""
    bound = 4 * Simulator.HEAP_COMPACT_MIN
    peak = _heap_churn()
    assert peak <= bound, f"heap peaked at {peak} > {bound}"
    peak_off = _heap_churn(compact_min=10**9)
    assert peak_off > peak, (peak, peak_off)


def test_compaction_threshold_is_result_invariant():
    """Compacting aggressively (threshold 1) vs never compacting
    yields the same SimResult — compaction only sweeps entries whose
    engine token already moved on, never a live event."""
    def run(threshold):
        cluster = NPUCluster(core=_CHURN_CORE, policy="neu10")
        sim = Simulator((), policy="neu10", core=_CHURN_CORE)
        sim.HEAP_COMPACT_MIN = threshold
        hs = []
        for i, w in enumerate(("DLRM", "ENet")):
            h = cluster.register_vnpu(
                w, get_workload(w, _CHURN_CORE),
                VNPUConfig(2, 2, hbm_bytes=64 << 20, sram_bytes=1 << 20))
            idx = sim.add_tenant(
                TenantSpec(cluster.compile(h.trace), h.vnpu),
                open_loop=True)
            hs.append((h, idx))
            for j in range(6):
                sim.inject_request(idx, j * 3_000.0)
        sim.run_until(20_000.0)
        h, idx = hs.pop(0)
        sim.remove_tenant(idx)        # cancellations feed the ledger
        cluster.deregister(h)
        sim.run_until(math.inf)
        return sim.result()

    assert run(1) == run(10**9)


# ----------------------------------------------------------------------
# cluster event heap (ServingSession._advance)
# ----------------------------------------------------------------------
def _fabric_run(fast_path):
    sess = ServingSession(
        NPUCluster(core=KV_CORE, policy="neu10",
                   topology=FabricTopology.ring(4)),
        fast_path=fast_path)
    ft = sess.register_generative(
        "chat", CHAT, prompt_len=128, gen_lens=8, eu_budget=4,
        placement=Placement(prefill_core=0, decode_core=2),
        kv_policy="evict", hbm_bytes=256 * SEG)
    sess.submit_arrivals(ft, PoissonArrivals(rate_rps=150.0, n=16, seed=7))
    sess.drain()
    return sess, ft


def test_cluster_heap_wakes_idle_migration_target():
    """The decode core starts with an INFINITE event horizon (no
    local work): only the migration hook's re-key can wake it in the
    cluster event heap. Every hand-off must land and decode there —
    and the whole run must be identical on the default and the
    reference engine."""
    sess, ft = _fabric_run(True)
    r = sess.report(ft)[0]
    assert r.requests_done == 16
    assert r.kv_migrations == 16
    assert ft.decode.vnpu.kv_ledger.in_use == 0
    sess_ref, ft_ref = _fabric_run(False)
    for s_inc, s_ref in zip(sess.sims, sess_ref.sims):
        assert s_inc.result() == s_ref.result()
