"""Refcounted cross-request KV prefix sharing + cross-tenant HBM
borrowing: ledger property suite vs a mirror model, sharing-off golden
bit-identity across both engines, composition tests (shared-
holder eviction, borrow/reclaim ordering, cross-core migration of a
shared-prefix holder), and the shrink-with-resident-shared-segments
resize regression."""
import pytest

from repro.configs import SMOKES
from repro.core.fabric import FabricTopology, Placement
from repro.core.mapper import ReconfigureError
from repro.core.policies import pick_eviction_victim
from repro.core.vnpu import KVLedger, KVLedgerError
from repro.npu.hw_config import DEFAULT_CORE
from repro.npu.trace import request_plan
from repro.serve.session import (GenLenDistribution, NPUCluster,
                                 PoissonArrivals, PrefixProfile,
                                 ServingSession)
from tests.hypothesis_compat import given, settings, st

CFG = SMOKES["qwen2-0.5b"]
SEG = 64 * 1024
SMALL_CORE = DEFAULT_CORE.with_(hbm_bytes=1024 * SEG, hbm_segment=SEG)
WEIGHTS = CFG.param_count() * 2
WSEG = -(-WEIGHTS // SEG) * SEG


# ----------------------------------------------------------------------
# PrefixProfile: deterministic, monotone in the ratio, validated
# ----------------------------------------------------------------------
def test_prefix_profile_deterministic_and_monotone():
    lo = PrefixProfile(prefix_len=64, share_ratio=0.3, n_prefixes=3, seed=9)
    hi = PrefixProfile(prefix_len=64, share_ratio=0.8, n_prefixes=3, seed=9)
    a, b = lo.sample(200, stream=4), lo.sample(200, stream=4)
    assert (a == b).all()                       # same (seed, stream)
    assert (a != lo.sample(200, stream=5)).any()
    klo, khi = lo.sample(200, stream=4), hi.sample(200, stream=4)
    # raising the ratio only ADDS shared arrivals: every request shared
    # at the low ratio keeps its exact group at the high ratio
    assert all(h == l for l, h in zip(klo, khi) if l != 0)
    assert (khi != 0).sum() > (klo != 0).sum()
    assert set(khi) <= {0, 1, 2, 3}


def test_prefix_profile_validation():
    with pytest.raises(ValueError, match="prefix_len"):
        PrefixProfile(prefix_len=0)
    with pytest.raises(ValueError, match="share_ratio"):
        PrefixProfile(prefix_len=8, share_ratio=1.5)
    with pytest.raises(ValueError, match="n_prefixes"):
        PrefixProfile(prefix_len=8, n_prefixes=0)


def test_register_prefix_profile_validation():
    sess = ServingSession(NPUCluster(core=SMALL_CORE, policy="neu10"))
    prof = PrefixProfile(prefix_len=64)
    with pytest.raises(ValueError, match="kv_policy"):
        sess.register_generative("t0", CFG, prompt_len=128,
                                 gen_lens=8, eu_budget=4,
                                 prefix_profile=prof)
    with pytest.raises(ValueError, match="kv_borrow"):
        sess.register_generative("t1", CFG, prompt_len=128,
                                 gen_lens=8, eu_budget=4, kv_borrow=True)
    with pytest.raises(ValueError, match="unshared suffix"):
        # the prefix must leave at least one suffix token
        request_plan(CFG, 1, 64, 8, core=SMALL_CORE, prefix_len=64)
    # a plan built with a DIFFERENT prefix_len than the profile's is
    # a caller bug, not something to paper over
    plan = request_plan(CFG, 1, 128, 8, core=SMALL_CORE, prefix_len=32)
    with pytest.raises(ValueError, match="does not match"):
        sess.cluster.register("t2", plan.profile_trace(), eu_budget=4,
                              plan=plan, kv_policy="evict",
                              hbm_bytes=WSEG + 8 * SEG,
                              prefix_profile=prof)
    # a prefix-keyed arrival against a tenant without the machinery
    # must fail loudly, not silently drop the key
    chat = sess.register_generative("t3", CFG, prompt_len=128,
                                    gen_lens=8, eu_budget=4,
                                    kv_policy="evict",
                                    hbm_bytes=WSEG + 8 * SEG)
    with pytest.raises(ValueError, match="prefix"):
        sess.submit(chat, prefix_key=5)


# ----------------------------------------------------------------------
# eviction policy: multi-ref shared holders go LAST
# ----------------------------------------------------------------------
class _FakePlan:
    has_decode = False


class _FakeReq:
    def __init__(self, arrival, gen_len, tokens_done=1, refs=0):
        self.arrival = arrival
        self.gen_len = gen_len
        self.tokens_done = tokens_done
        self.refs = refs


def test_eviction_victim_spares_multi_ref_shared_holders():
    # r2 has the largest remaining service — the legacy pick — but it
    # holds a shared entry other requests still reference
    r0 = _FakeReq(arrival=0.0, gen_len=4)
    r1 = _FakeReq(arrival=1.0, gen_len=9, refs=1)     # sole holder
    r2 = _FakeReq(arrival=2.0, gen_len=50, refs=3)    # multi-ref
    reqs = [r0, r1, r2]
    ctx = lambda r: 0
    # without the callback: pure PREMA estimate picks r2
    assert pick_eviction_victim(reqs, _FakePlan(), ctx) is r2
    # with it: multi-ref holders are last-out; r1 (refcount 1) counts
    # as evictable like any private request and out-estimates r0
    pick = pick_eviction_victim(reqs, _FakePlan(), ctx,
                                shared_refs_of=lambda r: r.refs)
    assert pick is r1
    # all candidates multi-ref: the picker still returns one (the
    # ledger falls back to swapping suffixes, never deadlocks)
    allshared = [_FakeReq(0.0, 5, refs=2), _FakeReq(1.0, 9, refs=2)]
    assert pick_eviction_victim(allshared, _FakePlan(), ctx,
                                shared_refs_of=lambda r: r.refs) \
        is allshared[1]


# ----------------------------------------------------------------------
# property suite: shared + borrow interleavings vs a mirror model
# ----------------------------------------------------------------------
_PREFIX_OPS = st.lists(
    st.tuples(
        st.sampled_from(["alloc", "grow", "free", "acq", "rel",
                         "borrow", "reclaim", "resize"]),
        st.integers(min_value=0, max_value=1),        # acting ledger
        st.integers(min_value=0, max_value=4),        # rid / prefix key
        st.integers(min_value=0, max_value=3 * SEG),  # bytes
    ),
    max_size=80,
)


def _kb(key: int) -> int:
    """Shared-entry size as a pure function of the key (the hash names
    the exact token range)."""
    return (key + 1) * SEG // 2


@given(ops=_PREFIX_OPS,
       caps=st.lists(st.integers(min_value=2, max_value=12),
                     min_size=2, max_size=2))
@settings(max_examples=150, deadline=None)
def test_shared_borrow_interleavings_match_mirror(ops, caps):
    """Any interleaving of alloc/grow/free, shared acquire/release,
    manager-style borrow (lend+grant) / reclaim (revoke+reclaim_lent),
    and capacity resize against a lender/borrower ledger pair matches
    a mirror model: refcounts never go negative, byte totals are
    conserved, ``reserved + in_use + shared_in_use + lent`` never
    exceeds ``capacity + borrowed``, and a full drain leaks nothing."""
    pytest.importorskip("hypothesis")
    leds = [KVLedger(c * SEG, SEG, reserved_bytes=SEG) for c in caps]
    entries = [dict() for _ in leds]          # rid -> bytes
    shared = [dict() for _ in leds]           # key -> [bytes, refs]
    loan = 0                                  # ledger 0 lends TO 1

    def check():
        for led, ent, shr in zip(leds, entries, shared):
            assert led.in_use == sum(ent.values())
            assert led.entries == ent
            assert led.shared == shr
            assert led.shared_in_use == sum(b for b, _ in shr.values())
            assert all(r >= 1 for _, r in shr.values())
            assert (led.reserved + led.in_use + led.shared_in_use
                    + led.lent <= led.capacity + led.borrowed)
        assert leds[0].lent == loan == leds[1].borrowed

    for op, i, rid, n in ops:
        led, ent, shr = leds[i], entries[i], shared[i]
        if op in ("alloc", "grow"):
            if led.alloc(rid, n):
                ent[rid] = ent.get(rid, 0) + n
            else:
                assert n > led.available      # reject only on pressure
        elif op == "free":
            if rid in ent:
                assert led.free(rid) == ent.pop(rid)
            else:
                with pytest.raises(KVLedgerError):
                    led.free(rid)
                assert led.release(rid) == 0  # lenient twin: no raise
        elif op == "acq":
            pb = _kb(rid)
            ok = led.acquire_shared(rid, pb)
            if rid in shr:
                assert ok
                shr[rid][1] += 1
            elif ok:
                shr[rid] = [pb, 1]
            else:
                assert pb > led.available
        elif op == "rel":
            if rid in shr:
                shr[rid][1] -= 1
                freed = led.release_shared(rid)
                if shr[rid][1] == 0:
                    assert freed == shr.pop(rid)[0]
                else:
                    assert freed == 0
            else:
                with pytest.raises(KVLedgerError):
                    led.release_shared(rid)
        elif op == "borrow":                  # manager pairing: 0 -> 1
            take = (n // SEG) * SEG
            if take > 0 and leds[0].lend(take):
                leds[1].grant(take)
                loan += take
            elif take > 0:
                assert take > leds[0].available
        elif op == "reclaim":                 # revoke idle, then unlend
            back = leds[1].revoke(min(n, loan))
            assert 0 <= back <= min(n, loan)
            leds[0].reclaim_lent(back)
            loan -= back
        else:                                 # resize via migrate_from
            newcap = max(n, SEG)
            fresh = KVLedger(newcap, SEG)
            need = (led.reserved + led.in_use + led.shared_in_use
                    + led.lent)
            if need > newcap + led.borrowed:
                with pytest.raises(KVLedgerError):
                    fresh.migrate_from(led)   # shrink rejected...
            else:
                fresh.migrate_from(led)       # ...or carried EXACTLY
                assert fresh.entries == ent
                assert fresh.shared == shr
                assert fresh.lent == led.lent
                assert fresh.borrowed == led.borrowed
                leds[i] = fresh
        check()

    # drain: free every rid and release every refcount — zero leak
    for led, ent, shr in zip(leds, entries, shared):
        for rid in list(ent):
            led.free(rid)
        for key, (_, refs) in list(shr.items()):
            for _ in range(refs):
                led.release_shared(key)
        assert led.in_use == 0 and led.shared_in_use == 0
        assert not led.entries and not led.shared


def test_acquire_shared_size_mismatch_raises():
    led = KVLedger(8 * SEG, SEG)
    assert led.acquire_shared(7, 2 * SEG)
    with pytest.raises(KVLedgerError, match="collision"):
        led.acquire_shared(7, SEG)            # hash collision guard
    assert led.shared_refs(7) == 1            # nothing changed


# ----------------------------------------------------------------------
# sharing-off golden bit-identity (both engines)
# ----------------------------------------------------------------------
# Captured from the PR 7 tree on the fixed pressure / fabric scenarios
# below: with no prefix_profile and no kv_borrow, the sharing-capable
# engine must not perturb a single event — every counter, latency sum
# and the final event clock stay byte-identical, for the default
# (incremental) AND reference (fast_path off) engines.
#
# "pressure/evict"'s tbt sum (10th field) is re-pinned from the
# captured 8195041.509125 to 8195041.509126, the value both engines
# give: the unrounded sum of its 2,108 terms is 8195041.509125591,
# 9.1e-8 (about 49 ulps) above the rounding boundary .5091255, so the
# capture's floating-point environment rounded it the other way. The
# captured value fails at the capturing commit too; every counter and
# the other three sums match as captured.
PREFIX_OFF_GOLDEN = {
    "pressure/evict": [24, 2132, 30, 30, 0, 0, 0,
                       12212025.457979, 4016983.948854,
                       8195041.509126, 1624303.296264],
    "pressure/reject": [24, 3088, 42, 0, 42, 0, 0,
                        17339038.22723, 9544632.355104,
                        6852367.88375, 2498489.253263],
    "fabric": [[0, 20, 20, 655360.0, 0, 62566.72, 0],
               [20, 140, 0, 0.0, 406532.357501, 0, 343965.637501],
               103331327.755612],
}


def _pressure_stats(kv_policy, engine):
    cluster = NPUCluster(core=SMALL_CORE, policy="neu10")
    sess = ServingSession(cluster, fast_path=(engine != "ref"))
    chat = sess.register_generative(
        "chat", CFG, prompt_len=128,
        gen_lens=GenLenDistribution(mean=96.0, max_len=256, seed=11),
        eu_budget=4, kv_policy=kv_policy,
        hbm_bytes=WSEG + 2 * SEG)
    sess.submit_arrivals(chat, PoissonArrivals(rate_rps=200_000.0,
                                               n=24, seed=1))
    sess.drain()
    st_ = sess.sim.tenants[chat.sim_idx].stats
    return [st_.requests_done, st_.tokens, st_.kv_evictions,
            st_.kv_swapins, st_.kv_restarts, st_.kv_rejected,
            st_.kv_truncated,
            round(sum(st_.latencies), 6), round(sum(st_.ttft), 6),
            round(sum(st_.tbt), 6), round(sess.sim.now, 6)]


def _fabric_stats(engine):
    sess = ServingSession(
        NPUCluster(core=SMALL_CORE, policy="neu10",
                   topology=FabricTopology.mesh(4)),
        fast_path=(engine != "ref"))
    ft = sess.register_generative(
        "chat", CFG, prompt_len=128, gen_lens=8, eu_budget=4,
        placement=Placement(), kv_policy="evict", hbm_bytes=256 * SEG)
    sess.submit_arrivals(ft, PoissonArrivals(rate_rps=200.0, n=20, seed=1))
    sess.drain()
    out = []
    for h in (ft.prefill, ft.decode):
        st_ = sess.sims[h.core_idx].tenants[h.sim_idx].stats
        out.append([st_.requests_done, st_.tokens, st_.kv_migrations,
                    round(st_.kv_migrated_bytes, 6),
                    round(sum(st_.latencies), 6), round(sum(st_.ttft), 6),
                    round(sum(st_.tbt), 6)])
    out.append(round(max(s.now for s in sess.sims), 6))
    return out


@pytest.mark.parametrize("engine", ["inc", "ref"])
@pytest.mark.parametrize("kv_policy", ["evict", "reject"])
def test_sharing_off_pressure_goldens_bit_identical(kv_policy, engine):
    assert (_pressure_stats(kv_policy, engine)
            == PREFIX_OFF_GOLDEN[f"pressure/{kv_policy}"])


@pytest.mark.parametrize("engine", ["inc", "ref"])
def test_sharing_off_fabric_golden_bit_identical(engine):
    assert _fabric_stats(engine) == PREFIX_OFF_GOLDEN["fabric"]


# ----------------------------------------------------------------------
# sharing ON: determinism, engine agreement, pressure composition
# ----------------------------------------------------------------------
def _sharing_session(kv_segs=4, ratio=1.0, n=24, engine="inc",
                     gen_mean=96.0, **reg_kw):
    cluster = NPUCluster(core=SMALL_CORE, policy="neu10")
    sess = ServingSession(cluster, fast_path=(engine != "ref"))
    chat = sess.register_generative(
        "chat", CFG, prompt_len=128,
        gen_lens=GenLenDistribution(mean=gen_mean, max_len=256, seed=11),
        eu_budget=4, kv_policy="evict", hbm_bytes=WSEG + kv_segs * SEG,
        prefix_profile=PrefixProfile(prefix_len=64, share_ratio=ratio,
                                     n_prefixes=1, seed=3), **reg_kw)
    sess.submit_arrivals(chat, PoissonArrivals(rate_rps=200_000.0,
                                               n=n, seed=1))
    return sess, chat


def _sharing_fingerprint(sess, chat):
    st_ = sess.sim.tenants[chat.sim_idx].stats
    return (st_.requests_done, st_.tokens, st_.kv_prefix_hits,
            round(st_.kv_shared_bytes, 6), st_.kv_evictions,
            st_.kv_swapins, round(sum(st_.latencies), 6),
            round(sess.sim.now, 6))


def test_same_seed_hit_miss_sequence_is_deterministic():
    """Two identical sharing runs produce the SAME hit/miss sequence,
    counters and event clock — and both engines agree."""
    prints = []
    for engine in ("inc", "inc", "ref"):
        sess, chat = _sharing_session(engine=engine)
        sess.drain()
        prints.append(_sharing_fingerprint(sess, chat))
    assert prints[0] == prints[1] == prints[2]
    assert prints[0][2] > 0                   # hits actually happened


def test_evict_mode_swapin_with_resident_prefix():
    """Tight budget + fully-shared prefixes: evictions and swap-resume
    round trips interleave with prefix hits, every request completes,
    and BOTH pools (per-rid and refcounted) drain to zero."""
    sess, chat = _sharing_session(kv_segs=2, ratio=1.0)
    sess.drain()
    st_ = sess.sim.tenants[chat.sim_idx].stats
    assert st_.requests_done == 24
    assert st_.kv_evictions >= 1 and st_.kv_swapins >= 1
    assert st_.kv_prefix_hits >= 1
    led = chat.vnpu.kv_ledger
    assert led.peak_bytes <= led.capacity     # never over-committed
    assert led.in_use == 0 and led.shared_in_use == 0
    assert not led.entries and not led.shared


def test_prefix_hits_charge_suffix_only():
    """On a roomy budget the shared arm's peak ledger occupancy stays
    BELOW the unshared arm's — hits charge the suffix, not the whole
    prompt (the effective-capacity mechanism, asserted on bytes)."""
    sess_on, chat_on = _sharing_session(kv_segs=24, ratio=1.0,
                                        gen_mean=8.0)
    sess_on.drain()
    sess_off, chat_off = _sharing_session(kv_segs=24, ratio=0.0,
                                          gen_mean=8.0)
    sess_off.drain()
    on = sess_on.sim.tenants[chat_on.sim_idx].stats
    off = sess_off.sim.tenants[chat_off.sim_idx].stats
    assert on.requests_done == off.requests_done == 24
    assert on.kv_prefix_hits > 0 and off.kv_prefix_hits == 0
    assert on.kv_peak_bytes < off.kv_peak_bytes


# ----------------------------------------------------------------------
# cross-tenant borrowing: pressure relief + reclaim ordering
# ----------------------------------------------------------------------
def test_borrow_then_owner_burst_reclaims_loans():
    """A squeezed borrower takes idle segments from a co-resident
    owner; when the owner's OWN load arrives, its pressure hook
    reclaims the (by then idle) loans before its admission blocks —
    both tenants complete everything, loans unwind, zero leak."""
    cluster = NPUCluster(core=SMALL_CORE, policy="neu10")
    sess = ServingSession(cluster)
    needy = sess.register_generative(
        "needy", CFG, prompt_len=128,
        gen_lens=GenLenDistribution(mean=96.0, max_len=256, seed=11),
        eu_budget=2, kv_policy="evict", hbm_bytes=WSEG + 2 * SEG,
        kv_borrow=True)
    owner = sess.register_generative(
        "owner", CFG, prompt_len=128, gen_lens=64, eu_budget=2,
        kv_policy="evict", hbm_bytes=WSEG + 8 * SEG)
    man = cluster.manager
    # phase 1: the needy burst borrows the owner's idle segments
    sess.submit_arrivals(needy, PoissonArrivals(rate_rps=200_000.0,
                                                n=24, seed=1))
    sess.run_until(0.01)
    n_st = sess.sim.tenants[needy.sim_idx].stats
    assert n_st.kv_borrowed_bytes > 0
    lent0 = man.loans_of(owner.vnpu)[0]
    assert lent0 > 0
    assert owner.vnpu.kv_ledger.lent == lent0
    assert needy.vnpu.kv_ledger.borrowed == lent0
    # phase 2: the owner bursts — reclaim-on-pressure pulls the loans
    # back (the needy burst is drained, so its segments are idle)
    for i in range(8):
        sess.submit(owner, at_s=0.01 + i * 1e-6)
    sess.drain()
    o_st = sess.sim.tenants[owner.sim_idx].stats
    assert o_st.kv_reclaimed_bytes > 0
    assert man.loans_of(owner.vnpu)[0] < lent0
    assert n_st.requests_done == 24 and o_st.requests_done == 8
    oled, nled = owner.vnpu.kv_ledger, needy.vnpu.kv_ledger
    assert oled.in_use == 0 and nled.in_use == 0
    # conservation: whatever loan remains is symmetric on both sides
    assert oled.lent == nled.borrowed == man.loans_of(owner.vnpu)[0]


def test_borrow_disabled_never_touches_peers():
    """Without kv_borrow the same squeeze stays inside the tenant's
    own allocation: no loans, the co-resident ledger untouched — and
    the run is bit-identical to the single-tenant golden."""
    cluster = NPUCluster(core=SMALL_CORE, policy="neu10")
    sess = ServingSession(cluster)
    chat = sess.register_generative(
        "chat", CFG, prompt_len=128,
        gen_lens=GenLenDistribution(mean=96.0, max_len=256, seed=11),
        eu_budget=2, kv_policy="evict", hbm_bytes=WSEG + 2 * SEG)
    idle = sess.register_generative(
        "idle", CFG, prompt_len=128, gen_lens=8, eu_budget=2,
        kv_policy="evict", hbm_bytes=WSEG + 8 * SEG)
    sess.submit_arrivals(chat, PoissonArrivals(rate_rps=200_000.0,
                                               n=24, seed=1))
    sess.drain()
    st_ = sess.sim.tenants[chat.sim_idx].stats
    assert st_.kv_borrowed_bytes == 0
    assert cluster.manager.loans_of(idle.vnpu) == (0, 0)
    assert idle.vnpu.kv_ledger.lent == 0
    assert chat.vnpu.kv_ledger.borrowed == 0
    assert st_.requests_done == 24


# ----------------------------------------------------------------------
# composition: cross-core migration of a shared-prefix holder
# ----------------------------------------------------------------------
def _fabric_prefix_session(dec_hbm=None, rate=200_000.0, n=20):
    sess = ServingSession(
        NPUCluster(core=SMALL_CORE, policy="neu10",
                   topology=FabricTopology.mesh(4)))
    ft = sess.register_generative(
        "chat", CFG, prompt_len=128, gen_lens=8, eu_budget=4,
        placement=Placement(decode_hbm_bytes=dec_hbm),
        kv_policy="evict", hbm_bytes=256 * SEG,
        prefix_profile=PrefixProfile(prefix_len=64, share_ratio=1.0,
                                     n_prefixes=1, seed=3))
    sess.submit_arrivals(ft, PoissonArrivals(rate_rps=rate, n=n, seed=1))
    return sess, ft


def test_fabric_migration_shared_holder_suffix_only_on_hit():
    """A migrating shared-prefix holder whose key is already resident
    on the decode core moves (and charges) ONLY its suffix; the first
    hand-off first-fills the destination entry. Wire bytes therefore
    undercut the sharing-off baseline, and both cores drain clean."""
    sess, ft = _fabric_prefix_session()
    sess.drain()
    r = sess.report(ft)[0]
    dec_rt = sess.sims[ft.decode.core_idx].tenants[ft.decode.sim_idx]
    assert r.requests_done == 20
    assert r.kv_migrations == 20
    assert r.kv_migration_rejects == 0
    assert dec_rt.stats.kv_prefix_hits > 0    # dst-side resident hits
    # sharing-off baseline on the same scenario moves full context
    plan = ft.prefill.plan
    full_wire = 20 * (plan.kv_prompt_bytes + plan.kv_token_bytes)
    assert r.kv_migrated_bytes < full_wire
    for h in (ft.prefill, ft.decode):
        led = h.vnpu.kv_ledger
        assert led.in_use == 0 and led.shared_in_use == 0
        assert not led.entries and not led.shared


def test_fabric_migration_reject_releases_dst_prefix_ref():
    """Destination pressure with sharing on: a rejected hand-off must
    leave ALL ledgers untouched — including the destination's shared
    pool (the attach is rolled back) — and the request still
    completes locally."""
    probe = request_plan(CFG, 1, 256, 1, core=SMALL_CORE)
    dec_hbm = -(-int(probe.weight_bytes
                     + 1.2 * probe.kv_prompt_bytes) // SEG) * SEG
    sess = ServingSession(
        NPUCluster(core=SMALL_CORE, policy="neu10",
                   topology=FabricTopology.ring(4)))
    ft = sess.register_generative(
        "chat", CFG, prompt_len=256, gen_lens=32, eu_budget=4,
        placement=Placement(decode_hbm_bytes=dec_hbm),
        kv_policy="evict", hbm_bytes=512 * SEG,
        prefix_profile=PrefixProfile(prefix_len=64, share_ratio=1.0,
                                     n_prefixes=1, seed=3))
    sess.submit_arrivals(ft, PoissonArrivals(rate_rps=3000.0, n=16, seed=3))
    sess.drain()
    r = sess.report(ft)[0]
    assert r.requests_done == 16              # rejects completed locally
    assert r.kv_migration_rejects >= 1
    assert r.kv_migrations + r.kv_migration_rejects == 16
    for h in (ft.prefill, ft.decode):
        led = h.vnpu.kv_ledger
        assert led.peak_bytes <= led.capacity
        assert led.in_use == 0 and led.shared_in_use == 0
        assert not led.entries and not led.shared


# ----------------------------------------------------------------------
# resize regression: shared + lent segments are never stranded
# ----------------------------------------------------------------------
def test_migrate_from_refuses_to_strand_shared_and_lent():
    led = KVLedger(12 * SEG, SEG, reserved_bytes=2 * SEG)
    assert led.alloc(1, 2 * SEG)
    assert led.acquire_shared(7, 3 * SEG)
    assert led.lend(2 * SEG)
    # occupancy = 2 (weights) + 2 (rid) + 3 (shared) + 2 (lent) = 9 seg
    small = KVLedger(8 * SEG, SEG)
    with pytest.raises(KVLedgerError, match="occupancy"):
        small.migrate_from(led)
    assert led.shared_refs(7) == 1            # source untouched
    assert led.lent == 2 * SEG
    fits = KVLedger(9 * SEG, SEG)
    fits.migrate_from(led)                    # exact carry at the floor
    assert fits.shared == {7: [3 * SEG, 1]}
    assert fits.lent == 2 * SEG and fits.in_use == 2 * SEG


def test_live_resize_keeps_shared_prefix_segments():
    """Session-level regression for the shrink audit: a mid-run resize
    while refcounted prefix entries are resident must keep the HBM
    allocation >= the FULL occupancy (weights + rid KV + shared) —
    shrinking the shared segments out from under their holders would
    corrupt every other holder's hit."""
    sess, chat = _sharing_session(kv_segs=4)
    sess.run_until(2e-4)
    led = chat.vnpu.kv_ledger
    assert led.shared_in_use > 0              # a prefix entry is live
    for eu in (6, 2, 4):
        try:
            sess.resize(chat, eu)
        except ReconfigureError:
            pass                              # reject is legal...
        led = chat.vnpu.kv_ledger
        # ...stranding resident shared segments is not
        assert led.capacity >= led.occupancy
        assert led.shared_in_use == sum(b for b, _ in led.shared.values())
    sess.drain()
    st_ = sess.sim.tenants[chat.sim_idx].stats
    assert st_.requests_done == 24
    led = chat.vnpu.kv_ledger
    assert led.in_use == 0 and led.shared_in_use == 0 and not led.shared


# ----------------------------------------------------------------------
# LRU retention window: zero-holder entries linger, revive for free,
# expire on schedule, and are first-choice eviction victims
# ----------------------------------------------------------------------
def test_retention_parks_and_revives_for_free():
    led = KVLedger(8 * SEG, SEG)
    led.retention_window = 100.0
    led.acquire_shared(7, 2 * SEG)
    assert led.release_shared(7, now=10.0) == 0   # parked, not freed
    assert led.shared_refs(7) == 0
    assert led.retired_bytes == 2 * SEG
    assert led.shared_in_use == 2 * SEG           # bytes stay charged
    assert led.acquire_shared(7, 2 * SEG)         # retention HIT...
    assert led.shared_refs(7) == 1
    assert led.retired_bytes == 0                 # ...revived in place
    with pytest.raises(KVLedgerError, match="collision"):
        led.release_shared(7, now=20.0)
        led.acquire_shared(7, SEG)                # size mismatch guard


def test_retention_expiry_and_pressure_eviction_order():
    led = KVLedger(8 * SEG, SEG)
    led.retention_window = 100.0
    for key, t in ((1, 0.0), (2, 50.0)):
        led.acquire_shared(key, SEG)
        led.release_shared(key, now=t)            # expire at 100 / 150
    assert led.expire_retired(now=99.0) == 0      # neither lapsed yet
    assert led.expire_retired(now=100.0) == SEG   # exactly on schedule
    assert led.retired_bytes == SEG
    # pressure eviction takes the oldest-expiry survivor
    assert led.evict_retired(SEG, now=120.0) == SEG
    assert led.retired_bytes == 0 and led.shared_in_use == 0


def test_retention_flush_clear_and_migrate_carry():
    led = KVLedger(8 * SEG, SEG)
    led.retention_window = 100.0
    led.acquire_shared(3, 2 * SEG)
    led.release_shared(3, now=5.0)
    dst = KVLedger(8 * SEG, SEG)
    dst.migrate_from(led)                         # failover carries the
    assert dst.retired_bytes == 2 * SEG           # retention table...
    assert dst.retention_window == 100.0          # ...and the window
    assert dst.acquire_shared(3, 2 * SEG)         # still revivable
    assert led.flush_retired() == 2 * SEG         # teardown frees all
    assert led.shared_in_use == 0
    led.acquire_shared(4, SEG)
    led.release_shared(4, now=6.0)
    led.clear()                                   # per-request wipe
    assert led.retired_bytes == 0 and led.shared_in_use == 0


def test_retention_off_is_inert():
    led = KVLedger(8 * SEG, SEG)                  # window defaults to 0
    led.acquire_shared(9, SEG)
    assert led.release_shared(9, now=10.0) == SEG  # frees immediately
    assert led.retired_bytes == 0 and led.shared_in_use == 0


def test_session_retention_turns_thrash_into_hits():
    """Same sharing workload, zero vs generous retention: with the
    window on, a prefix whose holders all drain before the next
    arrival revives from the retired table instead of re-filling."""
    base_sess, base_chat = _sharing_session(kv_segs=8, gen_mean=8.0)
    base_sess.drain()
    base = _sharing_fingerprint(base_sess, base_chat)

    sess, chat = _sharing_session(kv_segs=8, gen_mean=8.0,
                                  kv_retention_ms=50.0)
    sess.drain()
    led = chat.vnpu.kv_ledger
    st_ = sess.sim.tenants[chat.sim_idx].stats
    assert st_.requests_done == base[0]
    assert st_.kv_prefix_hits >= base_sess.sim.tenants[
        base_chat.sim_idx].stats.kv_prefix_hits
    assert led.in_use == 0
    assert led.shared_in_use == led.retired_bytes  # only retained bytes
    led.flush_retired()
    assert led.shared_in_use == 0
