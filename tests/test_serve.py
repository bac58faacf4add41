"""Serving: engine generation + the multi-tenant vNPU control plane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.npu.workloads import get_workload
from repro.serve.engine import ServeEngine
from repro.serve.vserve import MultiTenantServer


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "musicgen-large",
                                  "zamba2-7b"])
def test_engine_generate(arch):
    cfg = SMOKES[arch]
    eng = ServeEngine(cfg, max_seq=64)
    B, S = 2, 16
    if cfg.family == "audio":
        prompt = np.random.randint(0, cfg.vocab_size,
                                   (B, cfg.n_codebooks, S))
    else:
        prompt = np.random.randint(0, cfg.vocab_size, (B, S))
    res = eng.generate(prompt, n_new=4)
    assert res.tokens.shape[-1] == 4
    assert res.tokens.min() >= 0 and res.tokens.max() < cfg.vocab_size
    # greedy decode is deterministic
    res2 = eng.generate(prompt, n_new=4)
    np.testing.assert_array_equal(res.tokens, res2.tokens)


def undonated_greedy(eng, prompt, n_new):
    """Greedy tokens of the model's own programs, jitted without
    donation, so every state stays readable."""
    model, toks = eng.model, jnp.asarray(prompt, jnp.int32)
    cache = model.init_cache(toks.shape[0], eng.max_seq, eng.dtype)
    lg, cache = jax.jit(model.prefill)(eng.params, {"tokens": toks}, cache)
    decode, out = jax.jit(model.decode_step), []
    for i in range(n_new):
        nxt = jnp.argmax(lg[:, -1:], axis=-1).astype(jnp.int32)
        out.append(np.asarray(nxt))
        lg, cache = decode(eng.params, cache, {
            "tokens": nxt,
            "cache_index": jnp.asarray(toks.shape[1] + i, jnp.int32)})
    return np.concatenate(out, axis=-1)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen2-moe-a2.7b"])
def test_engine_donates_each_state_once(arch):
    """The engine's programs take the cache donated and write their K/V
    rows into it in place: two calls on one engine give the greedy
    tokens of undonated programs, no call reads a donated (deleted)
    buffer again, and each program consumes the state it is given."""
    cfg = SMOKES[arch]
    eng = ServeEngine(cfg, max_seq=32)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 12))
    first = eng.generate(prompt, n_new=5).tokens
    np.testing.assert_array_equal(first, eng.generate(prompt, 5).tokens)
    np.testing.assert_array_equal(first, undonated_greedy(eng, prompt, 5))

    cache = eng.model.init_cache(2, eng.max_seq, eng.dtype)
    lg, state = eng._prefill(eng.params,
                             {"tokens": jnp.asarray(prompt, jnp.int32)}, cache)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    _, state2 = eng._decode(eng.params, state, {
        "tokens": jnp.argmax(lg[:, -1:], -1).astype(jnp.int32),
        "cache_index": jnp.asarray(12, jnp.int32)})
    assert all(a.is_deleted() for a in jax.tree.leaves(state))
    assert not any(a.is_deleted() for a in jax.tree.leaves(state2))


def test_multitenant_server_end_to_end():
    srv = MultiTenantServer(policy="neu10")
    a = srv.register("bert", get_workload("BERT"), eu_budget=4)
    b = srv.register("dlrm", get_workload("DLRM"), eu_budget=4)
    # allocator gave BERT (ME-heavy) more MEs, DLRM more VEs
    assert a.allocation.n_me >= a.allocation.n_ve
    assert b.allocation.n_ve >= b.allocation.n_me
    res, reports = srv.simulate(n_requests=4)
    assert all(r.throughput_rps > 0 for r in reports)
    assert res.me_utilization() <= 1.0


def test_multitenant_policies_ordering():
    def run(policy):
        srv = MultiTenantServer(policy=policy)
        srv.register("rsnt", get_workload("RsNt"), eu_budget=4)
        srv.register("dlrm", get_workload("DLRM"), eu_budget=4)
        res, _ = srv.simulate(n_requests=4)
        return res

    neu = run("neu10")
    nh = run("neu10_nh")
    pmt = run("pmt")
    assert neu.total_throughput() >= nh.total_throughput()
    assert neu.total_throughput() > pmt.total_throughput()


def test_autoscale_to_slo():
    # neu10_nh: without harvesting the EU allocation determines the
    # latency (a solo tenant under neu10 harvests the whole core, so
    # scaling its own allocation would be a no-op)
    srv = MultiTenantServer(policy="neu10_nh")
    t = srv.register("enet", get_workload("ENet"), eu_budget=2,
                     slo_p95_ms=None)
    _, reports = srv.simulate(n_requests=3)
    base_p95 = reports[0].p95_ms
    # demand an SLO just under the 2-EU latency -> autoscaler must grow
    t.slo_p95_ms = base_p95 * 0.7
    reports = srv.autoscale_to_slo(n_requests=3, max_eus=8)
    assert t.eu_budget > 2
    assert reports[0].p95_ms < base_p95


def test_deregister_releases_resources():
    srv = MultiTenantServer(policy="neu10")
    a = srv.register("a", get_workload("MNIST"), eu_budget=4)
    srv.deregister(a)
    b = srv.register("b", get_workload("MNIST"), eu_budget=8)
    assert b.vnpu.config.n_eus <= 8
