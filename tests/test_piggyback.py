"""Piggybacked iterations with an adaptive per-iteration token budget
(SARATHI-SF): mixed chunk+decode trace costing with shared weight
reads counted once, bounded on-demand program compilation, the
budgeted scheduling loop's floor/cap edges, and the guarantee that an
UNSET budget stays bit-identical to the static-chunk/monolithic
engine."""
import pytest

from repro.configs import SMOKES
from repro.core.compiler import compile_request_plan
from repro.npu.cost_model import (PIGGYBACK_CHUNK_FLOOR, RequestPlan,
                                  batch_bucket)
from repro.npu.hw_config import DEFAULT_CORE
from repro.npu.trace import lm_trace, piggyback_trace, request_plan
from repro.serve.session import (NPUCluster, PoissonArrivals,
                                 ServingSession, run_closed_loop)

CFG = SMOKES["qwen2-0.5b"]


def _session(policy="neu10"):
    return ServingSession(NPUCluster(policy=policy))


def _tenant(sess, budget, gen=8, prompt=1024, name="g", **kw):
    return sess.register_generative(name, CFG, prompt_len=prompt,
                                    gen_lens=gen, eu_budget=4,
                                    iteration_token_budget=budget, **kw)


# ----------------------------------------------------------------------
# plan / trace construction
# ----------------------------------------------------------------------
def test_budget_plan_construction():
    plan = request_plan(CFG, batch=1, prompt_len=1024, gen_len=8,
                        iteration_token_budget=288)
    assert plan.piggybacked and plan.iteration_token_budget == 288
    assert plan.piggyback_builder is not None
    assert not plan.chunked          # dynamic slices, no static chunks


def test_budget_and_static_chunks_are_mutually_exclusive():
    with pytest.raises(ValueError, match="replaces"):
        request_plan(CFG, batch=1, prompt_len=1024, gen_len=8,
                     prefill_chunk_tokens=256, iteration_token_budget=288)
    with pytest.raises(ValueError, match=">= 0"):
        request_plan(CFG, batch=1, prompt_len=1024, gen_len=8,
                     iteration_token_budget=-1)


def test_unset_budget_is_bit_identical_plan():
    a = request_plan(CFG, batch=1, prompt_len=512, gen_len=16)
    b = request_plan(CFG, batch=1, prompt_len=512, gen_len=16,
                     iteration_token_budget=0)
    assert not a.piggybacked and not b.piggybacked
    assert [(o.name, o.me_cycles, o.ve_cycles, o.hbm_bytes, o.n_tiles)
            for o in a.prefill.ops] == \
           [(o.name, o.me_cycles, o.ve_cycles, o.hbm_bytes, o.n_tiles)
            for o in b.prefill.ops]
    assert [c for c, _ in a.decode] == [c for c, _ in b.decode]


def test_batch_bucket():
    assert batch_bucket(0) == 0
    assert [batch_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_piggyback_trace_counts_shared_weights_once():
    """The fused program's decode ops drop their parameter-streaming
    HBM share (the chunk already streamed those weights) but keep
    per-token traffic (KV stream, embedding gathers)."""
    chunk = lm_trace(CFG, 1, 256, "prefill", kv_prior=256,
                     include_head=False)
    dec = lm_trace(CFG, 4, 512, "decode")
    tr = piggyback_trace(CFG, 1, 256, 256, 4, 512, final=False)
    assert len(tr.ops) == len(chunk.ops) + len(dec.ops)
    merged_dec = tr.ops[len(chunk.ops):]
    streamed = {op.name for op in chunk.ops if op.weight_bytes > 0}
    saved = 0.0
    for got, orig in zip(merged_dec, dec.ops):
        assert got.name == orig.name
        if orig.name in streamed and orig.weight_bytes > 0:
            assert got.hbm_bytes == pytest.approx(
                orig.hbm_bytes - orig.weight_bytes)
            assert got.weight_bytes == 0.0
            saved += orig.weight_bytes
        else:   # per-token traffic is NOT deduped
            assert got.hbm_bytes == orig.hbm_bytes
    assert saved > 0
    # attn_decode's KV stream survives in full
    kv = [op for op in merged_dec if op.name == "attn_decode"]
    assert kv and all(op.hbm_bytes > 0 for op in kv)
    # lm_head only dedupes when the chunk carries it (final slice)
    fin = piggyback_trace(CFG, 1, 256, 256, 4, 512, final=True)
    head_non = [o for o in tr.ops if o.name == "lm_head"]
    head_fin = [o for o in fin.ops if o.name == "lm_head"]
    assert len(head_non) == 1           # decode side only, full cost
    assert head_non[0].weight_bytes > 0
    assert len(head_fin) == 2           # chunk emits token 1 + decode
    assert head_fin[1].weight_bytes == 0.0


def test_piggyback_trace_without_decode_is_plain_chunk():
    tr = piggyback_trace(CFG, 1, 256, 512, 0, 0, final=False,
                         include_head=True)
    ref = lm_trace(CFG, 1, 256, "prefill", kv_prior=512,
                   include_head=False)
    assert tr.name == ref.name
    assert [(o.name, o.me_cycles, o.hbm_bytes) for o in tr.ops] == \
           [(o.name, o.me_cycles, o.hbm_bytes) for o in ref.ops]


# ----------------------------------------------------------------------
# compiler: on-demand programs, memoized and bounded
# ----------------------------------------------------------------------
def test_piggyback_phase_memoizes_through_shared_cache():
    cluster = NPUCluster(policy="neu10")
    plan = request_plan(CFG, batch=1, prompt_len=1024, gen_len=8,
                        iteration_token_budget=288)
    c1 = cluster.compile_plan(plan)
    c2 = cluster.compile_plan(plan)
    assert c1.can_piggyback and c1.iteration_token_budget == 288
    p1 = c1.piggyback_phase(256, 256, 2, 1024, False)
    assert p1.kind == "piggyback" and p1.context == 512
    assert c1.piggyback_phase(256, 256, 2, 1024, False) is p1  # memo
    # a second compiled plan shares the ProgramCache: same program
    assert c2.piggyback_phase(256, 256, 2, 1024, False).program \
        is p1.program
    # a different quantized mix is a different program
    assert c1.piggyback_phase(256, 256, 4, 1024, False).program \
        is not p1.program


def test_plan_without_builder_rejects_piggyback():
    plan = RequestPlan(name="bare", prefill=lm_trace(CFG, 1, 256,
                                                     "prefill"))
    c = compile_request_plan(plan, DEFAULT_CORE, isa="neuisa")
    assert not c.can_piggyback
    with pytest.raises(ValueError, match="piggyback builder"):
        c.piggyback_phase(256, 0, 0, 0, True)


def test_program_cache_stays_bounded_under_load():
    """Quantized keys: many requests at many live batch sizes compile
    a bounded program set, not one per iteration."""
    sess = _session()
    h = _tenant(sess, budget=288, gen=12, prompt=1024)
    sess.submit_arrivals(h, PoissonArrivals(rate_rps=30_000.0, n=24,
                                            seed=7))
    sess.drain()
    st = sess.sim.tenants[h.sim_idx].stats
    assert st.requests_done == 24
    assert st.piggyback_iterations > 24      # multiple slices/request
    assert len(sess.cluster.programs) < 40   # bounded, shared


# ----------------------------------------------------------------------
# simulator: budgeted iterations
# ----------------------------------------------------------------------
def test_piggyback_carries_chunk_and_decode_tokens():
    """THE tentpole property: one iteration serves a prefill slice AND
    >= 2 live decode tokens; riders sample TBT there, TTFT samples
    only come from slice owners (one per request)."""
    sess = _session()
    h = _tenant(sess, budget=288, gen=16, prompt=1024)
    sess.submit(h, at_s=0.0)
    sess.submit(h, at_s=0.00002)
    sess.submit(h, at_s=0.00003)
    sess.drain()
    st = sess.sim.tenants[h.sim_idx].stats
    assert st.requests_done == 3 and st.tokens == 48
    assert st.piggyback_iterations >= 1
    assert st.max_piggyback_batch >= 2
    assert st.piggyback_decode_tokens >= 2
    assert len(st.ttft) == 3                 # co-riders never add TTFT
    assert len(st.tbt) == 48 - 3


def test_token_accounting_matches_chunked_and_monolithic():
    """The budget changes WHEN work runs, not what a request produces:
    same requests, same tokens, same TTFT/TBT sample counts as the
    static-chunk and monolithic engines."""
    outs = []
    for kw in ({}, {"prefill_chunk_tokens": 256},
               {"iteration_token_budget": 288}):
        sess = _session()
        h = sess.register_generative("g", CFG, prompt_len=1024,
                                     gen_lens=8, eu_budget=4, **kw)
        sess.submit(h, at_s=0.0)
        sess.submit(h, at_s=0.00002)
        sess.drain()
        st = sess.sim.tenants[h.sim_idx].stats
        outs.append((st.requests_done, st.tokens, len(st.ttft),
                     len(st.tbt)))
    assert outs[0] == outs[1] == outs[2]


def test_oversubscribed_budget_runs_decode_only_then_floors():
    """Budget smaller than the live decode batch leaves no room for a
    slice: ONE decode-only iteration runs, then the next slice is
    floored — prefill always progresses and every request finishes."""
    sess = _session()
    # budget 33: with >= 2 decoding requests (bucket 2), 33 - 2 < floor
    assert 33 - 2 < PIGGYBACK_CHUNK_FLOOR
    h = _tenant(sess, budget=33, gen=48, prompt=256)
    sim = sess.sim
    rt = sim.tenants[h.sim_idx]
    log = []
    orig = rt._start_iteration

    def spy(t):
        orig(t)
        if rt.in_request:
            log.append((rt.active_kind,
                        len(rt.prefilling) + len(rt.waiting) + (
                            1 if rt.piggy_req is not None else 0)))

    rt._start_iteration = spy
    for i in range(3):
        sess.submit(h, at_s=i * 0.00002)
    sess.drain()
    st = rt.stats
    assert st.requests_done == 3 and st.tokens == 3 * 48
    # a decode-only iteration ran while prefill work was pending...
    assert any(kind == "decode" and pending > 0 for kind, pending in log)
    # ...and piggybacked slices still progressed after it
    decode_idx = next(i for i, (k, p) in enumerate(log)
                      if k == "decode" and p > 0)
    assert any(k == "piggyback" for k, _ in log[decode_idx + 1:])


def test_budget_larger_than_prompt_is_single_final_slice():
    sess = _session()
    h = _tenant(sess, budget=288, gen=4, prompt=200)
    sess.submit(h, at_s=0.0)
    sess.drain()
    st = sess.sim.tenants[h.sim_idx].stats
    assert st.requests_done == 1 and st.tokens == 4
    assert st.piggyback_iterations == 1      # whole prompt in one slice
    assert st.prefill_chunks == 1


def test_final_partial_slice():
    """A prompt that is not a multiple of the budget ends on a partial
    slice capped at the remaining tokens."""
    sess = _session()
    h = _tenant(sess, budget=288, gen=2, prompt=300)
    sess.submit(h, at_s=0.0)
    sess.drain()
    st = sess.sim.tenants[h.sim_idx].stats
    assert st.requests_done == 1
    assert st.piggyback_iterations == 2      # 288 + 12 (remainder)


def test_mid_prefill_deregister_during_piggybacked_iteration():
    sess = _session()
    h = _tenant(sess, budget=96, gen=16, prompt=1024)
    sess.submit(h, at_s=0.0)
    sess.submit(h, at_s=0.0)
    sess.run_until(1e-5)              # somewhere mid-slice-chain
    sess.deregister(h)
    assert sess.drain() >= 0.0        # no deadlock, no orphaned state


def test_open_loop_determinism_with_budget():
    def run_once():
        sess = _session()
        h = _tenant(sess, budget=160, gen=12, prompt=1024)
        sess.submit_arrivals(h, PoissonArrivals(rate_rps=4000.0, n=8,
                                                seed=3))
        sess.drain()
        st = sess.sim.tenants[h.sim_idx].stats
        return (st.latencies, st.ttft, st.tbt, st.tokens,
                st.piggyback_iterations, st.piggyback_decode_tokens,
                st.max_piggyback_batch)

    assert run_once() == run_once()


def test_closed_loop_with_budget():
    cluster = NPUCluster(policy="neu10")
    cluster.register_generative("g", CFG, prompt_len=1024, gen_lens=8,
                                eu_budget=4, iteration_token_budget=288)
    res, reports = run_closed_loop(cluster, n_requests=3)
    st = res.tenants[0]
    assert st.requests_done >= 3
    assert st.tokens == st.requests_done * 8
    assert st.piggyback_iterations >= st.requests_done
    assert reports[0].ttft_p95_ms > 0


# ----------------------------------------------------------------------
# live budget control (the autoscaler-facing knob)
# ----------------------------------------------------------------------
def test_set_iteration_token_budget_live():
    sess = _session()
    h = _tenant(sess, budget=0, gen=16, prompt=1024)
    sess.submit(h, at_s=0.0)
    sess.run_until(1e-5)
    st = sess.sim.tenants[h.sim_idx].stats
    assert st.piggyback_iterations == 0      # knob off: PR-3 engine
    sess.set_iteration_token_budget(h, 160)
    sess.submit(h)
    sess.submit(h)
    sess.drain()
    assert st.piggyback_iterations >= 1      # knob on mid-run
    assert st.requests_done == 3 and st.tokens == 48
    sess.set_iteration_token_budget(h, 0)    # and off again
    assert sess.sim.tenants[h.sim_idx].plan.iteration_token_budget == 0


def test_disable_budget_mid_prefill_restarts_parked_requests():
    """Turning the knob OFF with a request parked mid-slice drops the
    partial ingestion explicitly (KV restart): the cursor resets to 0
    and the monolithic replay is a restart, not a silent
    double-count; requests still finish with exact token counts."""
    sess = _session()
    h = _tenant(sess, budget=96, gen=8, prompt=1024)
    sess.submit(h, at_s=0.0)
    sess.submit(h, at_s=0.0)
    rt = sess.sim.tenants[h.sim_idx]
    # advance until slices have been ingested but prefill is unfinished
    t = 0.0
    while not (rt.piggy_req is not None and rt.piggy_req.prefill_done > 0
               or any(r.prefill_done > 0 for r in rt.prefilling)):
        t += 2e-6
        sess.run_until(t)
        assert t < 1.0, "never observed a request mid-slice"
    sess.set_iteration_token_budget(h, 0)
    sess.drain()
    st = rt.stats
    assert st.requests_done == 2 and st.tokens == 16
    assert len(st.ttft) == 2 and len(st.tbt) == 14
    # the restart left no stale cursors behind
    assert rt.piggy_req is None and not rt.prefilling


def test_fast_path_identical_across_mid_run_tenant_churn():
    """Deregister-then-register while harvested chunks are still in
    flight: the joiner takes ownership of engines still running a
    departed neighbor's work, and the fast path's incremental
    squatter counts must follow (regression: a stale count skipped
    reclaims the reference pass performed, diverging the
    SimResult)."""
    from repro.core import VNPUConfig
    from repro.core.simulator import Simulator, TenantSpec
    from repro.npu.workloads import get_workload

    def run(fast):
        cluster = NPUCluster(policy="neu10")
        core = cluster.core
        half = dict(hbm_bytes=core.hbm_bytes // 2,
                    sram_bytes=core.sram_bytes // 2)
        a = cluster.register_vnpu("a", get_workload("DLRM", core),
                                  VNPUConfig(2, 2, **half))
        x = cluster.register_vnpu("x", get_workload("BERT", core),
                                  VNPUConfig(2, 2, **half))
        sim = Simulator((), policy="neu10", core=core, fast_path=fast)
        ia = sim.add_tenant(TenantSpec(cluster.compile(a.trace), a.vnpu),
                            open_loop=True)
        ix = sim.add_tenant(TenantSpec(cluster.compile(x.trace), x.vnpu),
                            open_loop=True)
        for i in range(12):           # x stays idle: its engines are
            sim.inject_request(ia, i * 2_000.0)   # harvested by a
        # advance until one of a's chunks is in flight on x's engines
        t = 0.0
        while not any(e.chunk is not None and e.owner == ix
                      and e.tenant == ia
                      for e in sim.mes + sim.ves):
            t += 100.0
            sim.run_until(t)
            assert t < 5e6, "a never harvested x's engines"
        sim.remove_tenant(ix)         # ownership released, a's chunks
        cluster.deregister(x)         # on x's engines keep running
        c = cluster.register_vnpu("c", get_workload("BERT", core),
                                  VNPUConfig(2, 2, **half))
        ic = sim.add_tenant(TenantSpec(cluster.compile(c.trace), c.vnpu),
                            open_loop=True)
        sim.inject_request(ic, sim.now)
        sim.inject_request(ic, sim.now)
        sim.run_until()
        return sim.result()

    fast, ref = run(True), run(False)
    assert fast.makespan == ref.makespan
    assert fast.tenants == ref.tenants


def test_fast_schedule_honors_subclassed_dispatch():
    """A Simulator subclass overriding dispatch() (the documented
    policy-facing API) must keep seeing every chunk even though the
    default engine's neu10 pass normally dispatches inline."""
    from repro.core.simulator import Simulator, TenantSpec

    seen = []

    class SpySim(Simulator):
        def dispatch(self, chunk, engines, t, harvested=False):
            seen.append(chunk.phase)
            super().dispatch(chunk, engines, t, harvested)

    cluster = NPUCluster(policy="neu10")
    h = cluster.register_generative("g", CFG, prompt_len=512,
                                    gen_lens=8, eu_budget=4,
                                    iteration_token_budget=160)
    cplan = cluster.compile_plan(h.plan)
    sim = SpySim([TenantSpec(cplan.prefill.program, h.vnpu, 2,
                             plan=cplan)],
                 policy="neu10", core=cluster.core)
    res = sim.run()
    assert res.tenants[0].requests_done >= 2
    assert seen and "piggyback" in seen


def test_set_iteration_token_budget_guards():
    sess = _session()
    chunked = sess.register_generative("c", CFG, prompt_len=1024,
                                       gen_lens=8, eu_budget=4,
                                       prefill_chunk_tokens=256)
    with pytest.raises(ValueError, match="replaces"):
        sess.set_iteration_token_budget(chunked, 160)
    fixed = sess.register_model(CFG, batch=1, seq=128, eu_budget=2)
    with pytest.raises(ValueError, match="not generative"):
        sess.set_iteration_token_budget(fixed, 160)
    sess2 = _session()
    gen = _tenant(sess2, budget=160, gen=8, prompt=512, name="g2")
    with pytest.raises(ValueError, match=">= 0"):
        sess2.set_iteration_token_budget(gen, -5)


# ----------------------------------------------------------------------
# per-rider context-bucket costing (the ROADMAP refinement): the
# decode side of a fused program is priced at each rider's OWN bucket
# instead of the largest live bucket
# ----------------------------------------------------------------------
def test_piggyback_trace_groups_cheaper_than_max_bucket():
    """Splitting a mixed-context batch into per-bucket decode groups
    strictly undercuts pricing everyone at the largest bucket (the
    small riders stop paying the big riders' KV stream). On a
    KV-dominated mix the whole ideal span drops too; per-token
    traffic still scales with the real rider count."""
    grouped = piggyback_trace(CFG, 1, 128, 256, 0, 0, final=False,
                              decode_groups=[(4, 512), (4, 8192)])
    at_max = piggyback_trace(CFG, 1, 128, 256, 8, 8192, final=False)
    _, _, gh = grouped.totals()
    _, _, mh = at_max.totals()
    assert gh < mh                       # less HBM: the KV stream shrank
    core = DEFAULT_CORE
    assert grouped.ideal_cycles(core.n_me, core.n_ve) \
        < at_max.ideal_cycles(core.n_me, core.n_ve)
    # weight dedupe spans groups: the later group's projection
    # matmuls stream nothing (the chunk + first group already paid
    # the weights), so the only decode-side weight stream left is the
    # lm_head the chunk did not carry (non-final slice)
    n_chunk = len(lm_trace(CFG, 1, 128, "prefill", kv_prior=256,
                           include_head=False).ops)
    dec_weighted = [o.name for o in grouped.ops[n_chunk:]
                    if o.weight_bytes > 0]
    assert dec_weighted == ["lm_head"]


def test_single_bucket_batch_keeps_legacy_cache_key():
    """A batch whose riders share one context bucket compiles through
    the pre-grouping key — program identity proves byte-identity."""
    cluster = NPUCluster(policy="neu10")
    plan = request_plan(CFG, batch=1, prompt_len=1024, gen_len=8,
                        iteration_token_budget=288)
    c = cluster.compile_plan(plan)
    legacy = c.piggyback_phase(256, 256, 2, 1024, False)
    via_groups = c.piggyback_phase(256, 256, 2, 1024, False,
                                   decode_groups=None)
    assert via_groups is legacy


def _mixed_bucket_run(coerce_to_max: bool):
    """Four fixed-length requests staggered so late slices ride a
    mature request (bucket 1024) and a fresh one (bucket 512) at
    once; ``batch=8`` puts the decode cost in the KV-stream-dominated
    regime the refinement targets. ``coerce_to_max`` re-instates the
    PR 4 costing (everyone at the largest live bucket) for the A/B
    comparison."""
    sess = _session()
    h = sess.register_generative("g", CFG, prompt_len=256, gen_lens=400,
                                 batch=8, eu_budget=4,
                                 iteration_token_budget=96)
    rt = sess.sim.tenants[h.sim_idx]
    if coerce_to_max:
        plan = rt.plan

        def at_max(cost_tokens, pos, final):
            if not rt.decoding:
                return plan.piggyback_phase(cost_tokens, pos, 0, 0, final)
            live = max(rt._context_of(r) for r in rt.decoding)
            ctx = plan.decode_phase_for(live).context
            bb = batch_bucket(len(rt.decoding))
            return plan.piggyback_phase(cost_tokens, pos, bb, ctx, final)

        rt._piggyback_phase_for = at_max
    for i in range(4):
        sess.submit(h, at_s=i * 3e-4)
    sess.drain()
    st = rt.stats
    assert st.requests_done == 4
    return st, rt.plan


def test_per_rider_bucket_costing_improves_small_rider_tbt():
    refined, plan = _mixed_bucket_run(coerce_to_max=False)
    legacy, _ = _mixed_bucket_run(coerce_to_max=True)
    # the refinement only changes program COST, not token bookkeeping
    assert refined.tokens == legacy.tokens
    assert len(refined.tbt) == len(legacy.tbt)
    # a multi-bucket fused program was really built and memoized
    grouped_keys = [k for k in plan._piggy_memo if k[5] is not None]
    assert grouped_keys, "no mixed-bucket iteration occurred"
    assert all(len(k[5]) >= 2 for k in grouped_keys)
    # the small rider's cadence improves: every rider's token lands
    # when the fused iteration ends, and those iterations got cheaper
    # (the 512-bucket riders stopped paying the 1024-bucket KV
    # stream), so total time-between-tokens strictly drops
    assert sum(refined.tbt) < sum(legacy.tbt)
    assert sum(refined.latencies) < sum(legacy.latencies)


def test_grouped_piggyback_cache_stays_bounded():
    """Mixed-bucket keys live on the same finite quantized grid: a
    long staggered run compiles a bounded program set."""
    sess = _session()
    h = sess.register_generative("g", CFG, prompt_len=256, gen_lens=400,
                                 eu_budget=4, iteration_token_budget=96)
    sess.submit_arrivals(h, PoissonArrivals(rate_rps=5_000.0, n=12, seed=5))
    sess.drain()
    rt = sess.sim.tenants[h.sim_idx]
    assert rt.stats.requests_done == 12
    assert len(rt.plan._piggy_memo) < 64
    assert len(sess.cluster.programs) < 96
