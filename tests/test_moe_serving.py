"""The MoE tenant served through ``ServeEngine`` agrees with a plain
float32 reference (``moe_reference.py``), at smoke widths on the CPU:
prefill plus decode through the cache, logits against logits. Each
fault an MoE server can have must break that agreement; no assignment
is dropped at any batch or length; the engine's routing counter counts
a known routing exactly."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moe_reference
from repro.configs import SMOKES
from repro.kernels import ops
from repro.models import moe as moe_mod
from repro.serve import tracing
from repro.serve.engine import ServeEngine

CFG = SMOKES["qwen2-moe-a2.7b"]
B, S, N_NEW, MAX_SEQ = 4, 40, 6, 48       # B * S = 160 prompt tokens > 128
# Both sides are float32; they differ by the order of their sums (the
# kernel's row tiles, the sorted rows, the cache's masked softmax) and
# by HIGHEST against the CPU's default f32 dot. The widest gap measured
# on these weights is 2.7e-6 of logits up to 4.1; the mildest planted
# fault, bf16 in the program's place, reads 0.77.
ATOL = RTOL = 2e-4


def random_params(cfg, seed, dtype=jnp.float32):
    """The program's layout, every leaf random: projections as the
    program inits them, biases N(0, 0.1^2), norm scales 1 + N(0, 0.1^2)."""
    from repro.models.registry import build_model

    params = build_model(cfg).init(jax.random.PRNGKey(seed), jnp.float32)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = str(path[-1].key)
        z = jax.random.normal(k, leaf.shape, jnp.float32) * 0.1
        if name in ("bq", "bk", "bv"):
            leaf = z
        elif name == "scale":
            leaf = 1.0 + z
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def prompts(seed=0, b=B, s=S):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, (b, s), dtype=np.int32)


def served_logits(eng, toks, n_new):
    """Greedy tokens of ``eng.generate`` and the logits the engine's
    programs give along them: (B, S + n_new - 1, V), prefill's for the
    prompt, then one decode step per served token but the last."""
    got = eng.generate(toks, n_new).tokens
    model = eng.model
    cache = model.init_cache(toks.shape[0], eng.max_seq, eng.dtype)
    lg, cache = jax.jit(model.prefill)(
        eng.params, {"tokens": jnp.asarray(toks)}, cache)
    out = [lg]
    decode = jax.jit(model.decode_step)
    for i in range(n_new - 1):
        lg, cache = decode(eng.params, cache, {
            "tokens": jnp.asarray(got[:, i:i + 1]),
            "cache_index": jnp.asarray(toks.shape[1] + i, jnp.int32)})
        out.append(lg)
    return got, np.asarray(jnp.concatenate(out, axis=1), np.float32)


def reference_logits(params, toks, got):
    seq = np.concatenate([toks, got[:, :-1]], axis=1)
    return np.asarray(moe_reference.logits(CFG, params, jnp.asarray(seq)))


def gap(eng, params):
    toks = prompts()
    got, lg = served_logits(eng, toks, N_NEW)
    ref = reference_logits(params, toks, got)
    # greedy tokens are the argmax of the logits the programs give
    np.testing.assert_array_equal(got, np.argmax(lg[:, S - 1:], -1))
    return lg, ref


@pytest.fixture(scope="module")
def params():
    return random_params(CFG, 3)


def test_prefill_and_decode_match_the_float32_reference(params):
    eng = ServeEngine(CFG, params=params, max_seq=MAX_SEQ)
    lg, ref = gap(eng, params)
    np.testing.assert_allclose(lg, ref, rtol=RTOL, atol=ATOL)


def _drop_past_capacity(monkeypatch):
    """Capacity routing as before dropless: each expert keeps at most
    1.25 x its mean load of rows, the rest fall through as zero."""
    orig = ops.gmm

    def gmm(x, w, sizes, layer=None):
        out = orig(x, w, sizes, layer)
        cap = math.ceil(x.shape[0] / sizes.shape[0] * 1.25)
        starts = jnp.cumsum(sizes) - sizes
        rank = jnp.arange(x.shape[0]) - jnp.repeat(
            starts, sizes, total_repeat_length=x.shape[0])
        return jnp.where((rank < cap)[:, None], out, 0)
    monkeypatch.setattr(moe_mod.ops, "gmm", gmm)
    return CFG, None


def _renormalised_gates(monkeypatch):
    return CFG.replace(moe_norm_topk=True), None


def _no_shared_gate(monkeypatch):
    def drop(p):
        p = jax.tree.map(lambda a: a, p)
        del p["layers"]["moe"]["shared_gate"]
        return p
    return CFG.replace(shared_expert_gate=False), drop


def _bf16(monkeypatch):
    return CFG, lambda p: jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)


@pytest.mark.parametrize("fault", [_drop_past_capacity, _renormalised_gates,
                                   _no_shared_gate, _bf16])
def test_each_planted_fault_breaks_the_agreement(params, monkeypatch, fault):
    cfg, change = fault(monkeypatch)
    served = change(params) if change else params
    dtype = jax.tree.leaves(served)[0].dtype
    eng = ServeEngine(cfg, params=served, max_seq=MAX_SEQ, dtype=dtype)
    lg, ref = gap(eng, params)
    assert not np.allclose(lg, ref, rtol=RTOL, atol=ATOL)


def test_every_assignment_is_computed_past_128_tokens(params):
    """A zero router ties every expert, so every token goes to the same
    top-k experts: each of them gets all 256 tokens, twice the capacity
    routing would have kept."""
    m = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    m["router"] = jnp.zeros_like(m["router"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 256, CFG.d_model))
    y, _, experts = moe_mod.moe_apply(m, CFG, x)
    assert int(experts) == CFG.n_experts_per_tok
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(moe_reference.moe(CFG, m, x)),
                               rtol=RTOL, atol=ATOL)


def test_routing_counts_a_known_routing_exactly(params):
    """With a zero router each layer routes every row to the first k
    experts: k experts a layer, in the prefill and in every decode step
    whose token is kept."""
    p = jax.tree.map(lambda a: a, params)
    p["layers"]["moe"]["router"] = jnp.zeros_like(
        p["layers"]["moe"]["router"])
    eng = ServeEngine(CFG, params=p, max_seq=MAX_SEQ)
    before = tracing.routing()
    eng.generate(prompts(b=2, s=8), 5)
    after = tracing.routing()
    k, L = CFG.n_experts_per_tok, CFG.n_layers
    assert {n: after[n] - before[n] for n in after if n != "moe_layers"} == {
        "prefill_runs": 1, "prefill_experts": L * k,
        "decode_runs": 4, "decode_experts": 4 * L * k}
    assert after["moe_layers"] == L

    dense = ServeEngine(SMOKES["qwen2-0.5b"], max_seq=16)
    dense.generate(prompts(b=2, s=8) % dense.cfg.vocab_size, 3)
    assert tracing.routing() == after
