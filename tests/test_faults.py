"""Fault injection + failover: schedule determinism, fabric / mapper
fault primitives, whole-vNPU evacuation round-trips, kill-and-restart
suspension with deadline/retry re-admission, graceful HBM degradation,
fault-free golden bit-identity across both engines, and a
property suite interleaving chaos with arrivals / resizes / borrows."""
import pytest

from repro.configs import SMOKES
from repro.core.fabric import FabricLink, FabricTopology
from repro.core.faults import FaultEvent, FaultSchedule
from repro.core.mapper import VNPUManager
from repro.core.vnpu import KVLedgerError, VNPUConfig
from repro.npu.hw_config import DEFAULT_CORE
from repro.serve.session import (NPUCluster, PoissonArrivals,
                                 PrefixProfile, ServingSession)
from tests.hypothesis_compat import given, settings, st

CFG = SMOKES["qwen2-0.5b"]
SEG = 64 * 1024
CORE = DEFAULT_CORE.with_(hbm_bytes=1024 * SEG, hbm_segment=SEG)
LINK = FabricLink(bandwidth=16.0, latency=400_000.0)
HBM = 256 * SEG


# ----------------------------------------------------------------------
# FaultEvent / FaultSchedule: validation + seeded determinism
# ----------------------------------------------------------------------
def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(at=0.0, kind="meteor")
    with pytest.raises(ValueError, match=">= 0"):
        FaultEvent(at=-1.0, kind="core_down", core=0)
    with pytest.raises(ValueError, match="needs a core"):
        FaultEvent(at=0.0, kind="core_down")
    with pytest.raises(ValueError, match="link"):
        FaultEvent(at=0.0, kind="link_degrade", link=(2, 2))
    with pytest.raises(ValueError, match="n_segments"):
        FaultEvent(at=0.0, kind="hbm_fault", core=0, n_segments=0)
    assert FaultEvent(at=1.0, kind="core_down", core=0,
                      recovery=2.0).transient
    assert not FaultEvent(at=1.0, kind="core_down", core=0).transient


def test_chaos_schedule_is_seed_deterministic():
    kw = dict(horizon=10.0, n_cores=4, links=[(0, 1), (1, 2)],
              core_fault_rate=3.0, link_fault_rate=2.0,
              hbm_fault_rate=1.0, transient_frac=0.5, recovery=1.0)
    a = FaultSchedule.chaos(seed=7, **kw)
    b = FaultSchedule.chaos(seed=7, **kw)
    assert list(a) == list(b)
    assert list(a) != list(FaultSchedule.chaos(seed=8, **kw))
    assert all(0.0 <= ev.at < 10.0 for ev in a)
    assert all(ev.at <= nxt.at for ev, nxt in zip(a.events, a.events[1:]))
    # every degrade injected with a matching restore inside the window
    degrades = sum(ev.kind == "link_degrade" for ev in a)
    restores = sum(ev.kind == "link_restore" for ev in a)
    assert restores == degrades


# ----------------------------------------------------------------------
# fabric link faults
# ----------------------------------------------------------------------
def test_link_degrade_outage_restore():
    topo = FabricTopology.ring(4, LINK)
    base = topo.transfer_cycles(0, 1, 1 << 20)
    topo.degrade_link(0, 1, 0.25)
    assert topo.transfer_cycles(0, 1, 1 << 20) > base
    topo.restore_link(0, 1)
    assert topo.transfer_cycles(0, 1, 1 << 20) == base
    # outage removes the link: the ring reroutes the long way around
    hops_before = topo.hops(0, 1)
    topo.degrade_link(0, 1, 0.0)
    assert topo.hops(0, 1) > hops_before
    topo.restore_link(0, 1)
    assert topo.hops(0, 1) == hops_before
    with pytest.raises(ValueError):
        topo.degrade_link(0, 2, 0.5)       # not a ring edge


# ----------------------------------------------------------------------
# mapper fault primitives
# ----------------------------------------------------------------------
def test_fail_core_restore_and_placement():
    man = VNPUManager(n_pnpus=2, core=CORE)
    v = man.create(VNPUConfig(1, 1, hbm_bytes=4 * SEG), core_hint=0)
    assert man.fail_core(0) == [v.vnpu_id]
    assert man.healthy_cores() == [1]
    w = man.create(VNPUConfig(1, 1, hbm_bytes=4 * SEG))
    assert man.core_index_of(w) == 1       # failed core never placed on
    with pytest.raises(RuntimeError):
        man.create(VNPUConfig(1, 1, hbm_bytes=4 * SEG), core_hint=0)
    man.restore_core(0)
    assert man.healthy_cores() == [0, 1]


def test_hbm_fault_conserves_census_and_guards_occupancy():
    man = VNPUManager(core=CORE)
    total = CORE.hbm_bytes // CORE.hbm_segment
    v = man.create(VNPUConfig(1, 1, hbm_bytes=4 * SEG))
    v.kv_ledger.alloc(1, 3 * SEG)
    with pytest.raises(KVLedgerError, match="evict first"):
        man.fault_hbm_segments(v, 2)       # 3 live segs can't fit in 2
    free0, res0, flt0, tot0 = man.hbm_census()[0]
    assert (free0, res0, flt0, tot0) == (total - 4, 4, 0, total)
    v.kv_ledger.free(1)
    assert man.fault_hbm_segments(v, 2) == 2 * SEG
    assert v.kv_ledger.capacity == 2 * SEG
    free1, res1, flt1, tot1 = man.hbm_census()[0]
    assert (free1, res1, flt1) == (total - 4, 2, 2)
    assert free1 + res1 + flt1 == tot1     # parked, not leaked
    # free-pool faults clamp and conserve too
    assert man.fault_free_hbm_segments(0, total) == total - 4
    f, r, x, tt = man.hbm_census()[0]
    assert (f, r) == (0, 2) and f + r + x == tt


# ----------------------------------------------------------------------
# session failover: shared scenario helpers
# ----------------------------------------------------------------------
def _serve(faults=None, failover="evacuate", n_cores=4, retry=True,
           deadline_ms=50.0, max_retries=3, engine="inc", **reg_kw):
    topo = FabricTopology.mesh(n_cores, LINK)
    cluster = NPUCluster(core=CORE, policy="neu10", topology=topo)
    sess = ServingSession(cluster, faults=faults, failover=failover,
                          fast_path=(engine != "ref"))
    kw = dict(deadline_ms=deadline_ms, max_retries=max_retries,
              retry_backoff_ms=0.05) if retry else {}
    kw.update(reg_kw)
    chat = sess.register_generative(
        "chat", CFG, prompt_len=256, gen_lens=32, eu_budget=4,
        kv_policy="evict", hbm_bytes=HBM, **kw)
    sess.submit_arrivals(chat, PoissonArrivals(rate_rps=100_000.0,
                                               n=24, seed=1))
    sess.drain()
    return sess, chat, sess.report(chat)[0]


def _census_ok(sess):
    return all(f + r + x == t
               for f, r, x, t in sess.cluster.manager.hbm_census())


TRANSIENT = FaultSchedule([FaultEvent(at=0.0002, kind="core_down",
                                      core=0, recovery=0.002)])


def test_evacuation_round_trip():
    sess, chat, r = _serve(TRANSIENT)
    assert r.requests_done == 24
    assert r.evacuations == 1 and r.faults_survived >= 1
    assert chat.core_idx != 0              # really moved off core 0
    assert r.evacuated_bytes > 0
    assert r.downtime_ms > 0 and r.availability < 1.0
    led = chat.vnpu.kv_ledger
    assert led.in_use == 0 and led.shared_in_use == 0
    assert _census_ok(sess)


def test_permanent_core_fault_evacuates_and_completes():
    sch = FaultSchedule([FaultEvent(at=0.0002, kind="core_down", core=0)])
    sess, chat, r = _serve(sch)
    assert r.requests_done == 24 and r.evacuations == 1
    assert 0 not in sess.cluster.manager.healthy_cores()
    assert _census_ok(sess)


def test_restart_failover_resumes_and_retries_to_completion():
    sess, chat, r = _serve(TRANSIENT, failover="restart")
    assert r.requests_done == 24
    assert r.evacuations == 0
    assert chat.core_idx == 0              # resumed on the home core
    assert r.retries >= 1 and r.retry_successes >= 1
    assert r.faults_survived >= 1          # the suspend/resume round-trip
    assert r.downtime_ms >= 2.0            # parked the whole outage
    assert _census_ok(sess)


def test_restart_without_retry_budget_drops_aborted_work():
    sess, chat, r = _serve(TRANSIENT, failover="restart", retry=False)
    assert r.requests_done < 24            # fault-aborted work is lost...
    assert r.retries == 0
    led = chat.vnpu.kv_ledger
    assert led.in_use == 0                 # ...but never leaked
    assert _census_ok(sess)


def test_evacuation_beats_restart_on_tail_latency():
    _, _, ev = _serve(TRANSIENT, failover="evacuate")
    _, _, rs = _serve(TRANSIENT, failover="restart")
    assert rs.p95_ms / ev.p95_ms >= 1.3


def test_deadline_misses_and_retry_exhaustion_are_counted():
    # a 2 ms outage under restart failover floods admission at resume;
    # with a 0.05 ms per-attempt deadline the backlogged tail expires,
    # re-enters once (max_retries=1), and the unlucky rest drop —
    # every path counted, nothing leaked
    sess, chat, r = _serve(TRANSIENT, failover="restart",
                           deadline_ms=0.05, max_retries=1)
    assert r.deadline_misses >= 1
    assert r.retries >= 1 and r.retry_successes >= 1
    assert r.retries_exhausted >= 1
    assert r.requests_done + r.retries_exhausted == 24
    assert chat.vnpu.kv_ledger.in_use == 0
    assert _census_ok(sess)


def test_hbm_fault_degrades_gracefully():
    sch = FaultSchedule([FaultEvent(at=0.0002, kind="hbm_fault", core=0,
                                    n_segments=4)])
    sess, chat, r = _serve(sch)
    assert r.requests_done == 24
    assert r.hbm_fault_segments == 4 and r.evacuations == 0
    led = chat.vnpu.kv_ledger
    assert led.capacity == HBM - 4 * SEG
    assert chat.hbm_bytes == led.capacity  # resizes honor the new size
    f, res, flt, tot = sess.cluster.manager.hbm_census()[0]
    assert flt == 4 and f + res + flt == tot
    assert led.in_use == 0


def test_hbm_fault_on_vacant_core_hits_free_pool():
    sch = FaultSchedule([FaultEvent(at=0.0002, kind="hbm_fault", core=3,
                                    n_segments=2)])
    sess, chat, r = _serve(sch)
    assert r.requests_done == 24 and r.hbm_fault_segments == 0
    f, res, flt, tot = sess.cluster.manager.hbm_census()[3]
    assert flt == 2 and f + res + flt == tot


def test_link_faults_reroute_without_breaking_service():
    sch = FaultSchedule([
        FaultEvent(at=0.0001, kind="link_degrade", link=(0, 1),
                   bw_scale=0.0),
        FaultEvent(at=0.0002, kind="core_down", core=0, recovery=0.002),
        FaultEvent(at=0.003, kind="link_restore", link=(0, 1)),
    ])
    sess, chat, r = _serve(sch)
    assert r.requests_done == 24
    assert r.evacuations == 1
    assert _census_ok(sess)


def test_evacuation_carries_shared_prefix_and_retention():
    prof = PrefixProfile(prefix_len=64, share_ratio=1.0, n_prefixes=1,
                         seed=3)
    sess, chat, r = _serve(TRANSIENT, prefix_profile=prof,
                           kv_retention_ms=5.0)
    assert r.requests_done == 24
    assert r.evacuations == 1
    assert r.kv_prefix_hits > 0
    led = chat.vnpu.kv_ledger
    assert led.in_use == 0
    # retained (zero-holder) entries are the only shared bytes left —
    # the retention table survived the evacuation — and flushing them
    # drains the ledger completely
    assert led.shared_in_use == led.retired_bytes
    led.flush_retired()
    assert led.shared_in_use == 0 and led.retired_bytes == 0
    assert _census_ok(sess)


# ----------------------------------------------------------------------
# fault-free golden bit-identity (both engines)
# ----------------------------------------------------------------------
# Captured from the PR 9 tree on the burst scenario above: with every
# fault knob off — no schedule, deadline/retry/retention zero — the
# failover-capable session must not perturb a single event, for the
# default (incremental) AND reference (fast_path off) engines, and
# an EMPTY schedule must match no schedule at all.
FAULT_OFF_GOLDEN = [24, 768, 0.332126]


def _fingerprint(sess, chat):
    st_ = sess.sims[chat.core_idx].tenants[chat.sim_idx].stats
    return [st_.requests_done, st_.tokens,
            round(max(s.now for s in sess.sims) / CORE.freq_hz * 1e3, 6)]


@pytest.mark.parametrize("engine", ["inc", "ref"])
@pytest.mark.parametrize("empty_schedule", [False, True])
def test_fault_free_golden_bit_identical(engine, empty_schedule):
    faults = FaultSchedule([]) if empty_schedule else None
    sess, chat, r = _serve(faults, retry=False, engine=engine)
    assert _fingerprint(sess, chat) == FAULT_OFF_GOLDEN
    assert r.faults_survived == 0 and r.availability == 1.0


# ----------------------------------------------------------------------
# property: chaos x arrivals x resizes x borrows, vs the conservation
# mirror (all-transient faults + retry budget => nothing is ever lost)
# ----------------------------------------------------------------------
@given(seed=st.integers(0, 31),
       core_rate=st.floats(0.0, 3.0),
       hbm_rate=st.floats(0.0, 2.0),
       link_rate=st.floats(0.0, 2.0),
       failover=st.sampled_from(["evacuate", "restart"]),
       resize_eus=st.sampled_from([0, 2, 6]))
@settings(max_examples=12, deadline=None)
def test_chaos_interleaving_conserves_everything(seed, core_rate,
                                                 hbm_rate, link_rate,
                                                 failover, resize_eus):
    """Whatever transient chaos hits the cluster — core outages, HBM
    segment faults, link degradation — interleaved with bursty
    arrivals, a mid-run EU resize, and cross-tenant borrowing: every
    request either completes or exhausts its (generous) retry budget,
    no ledger leaks a byte, and every core's HBM segments stay exactly
    conserved (free + resident + faulted == total)."""
    pytest.importorskip("hypothesis")
    topo = FabricTopology.mesh(4, LINK)
    sch = FaultSchedule.chaos(
        horizon=0.004, n_cores=4, links=list(topo.links), seed=seed,
        core_fault_rate=core_rate, hbm_fault_rate=hbm_rate,
        link_fault_rate=link_rate, transient_frac=1.0, recovery=0.0015,
        bw_scale=0.25)
    cluster = NPUCluster(core=CORE, policy="neu10", topology=topo)
    sess = ServingSession(cluster, faults=sch, failover=failover)
    tenants = []
    for i, n in ((0, 16), (1, 8)):
        h = sess.register_generative(
            f"t{i}", CFG, prompt_len=256, gen_lens=16, eu_budget=4,
            kv_policy="evict", hbm_bytes=HBM, kv_borrow=True,
            max_retries=12, retry_backoff_ms=0.05)
        sess.submit_arrivals(h, PoissonArrivals(rate_rps=50_000.0,
                                                n=n, seed=i + 1))
        tenants.append((h, n))
    if resize_eus:
        sess.run_until(0.0001)
        h0, n0 = tenants[0]
        if h0.sim_idx >= 0:                # may be parked mid-failover
            try:
                tenants[0] = (sess.resize(h0, resize_eus), n0)
            except RuntimeError:           # no room on a shrunken core
                pass
    sess.drain()
    assert all(f + r + x == t
               for f, r, x, t in cluster.manager.hbm_census())
    for h, n in tenants:
        rep = sess.report(h)[0]
        # transient faults + 12 retries: every arrival is accounted for
        assert rep.requests_done + rep.retries_exhausted == n
        if h.vnpu is not None:             # not parked at drain time
            led = h.vnpu.kv_ledger
            assert led.in_use == 0 and led.shared_in_use == 0
        lent, borrowed = cluster.manager.loans_of(h.vnpu) \
            if h.vnpu is not None else (0, 0)
        assert lent >= 0 and borrowed >= 0
