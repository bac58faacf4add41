"""Per-architecture smoke tests (deliverable f): every assigned arch,
reduced config, one forward/train step on CPU — shapes + no NaNs —
plus decode-vs-prefill consistency and analytic-param-count cross
checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SMOKES
from repro.models import build_model
from repro.launch.steps import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    k1, k2 = jax.random.split(KEY)
    if cfg.family == "audio":
        shape = (B, cfg.n_codebooks, S)
    else:
        shape = (B, S)
    batch = {
        "tokens": jax.random.randint(k1, shape, 0, cfg.vocab_size),
        "targets": jax.random.randint(k2, shape, 0, cfg.vocab_size),
    }
    if cfg.family == "vlm":
        batch["tokens"] = batch["tokens"][:, : S - cfg.n_patches]
        batch["targets"] = batch["targets"][:, : S - cfg.n_patches]
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_patches, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", sorted(SMOKES))
def test_smoke_train_step(arch):
    cfg = SMOKES[arch]
    model = build_model(cfg)
    state = init_train_state(model, KEY)
    step = jax.jit(make_train_step(model, warmup=1))
    batch = _batch(cfg)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # logits shape sanity via model.logits
    lg, _ = build_model(cfg, remat=False).logits(state["params"], batch)
    if cfg.family == "audio":
        assert lg.shape == (2, 32, cfg.n_codebooks, cfg.vocab_size)
    elif cfg.family == "vlm":
        assert lg.shape[-1] == cfg.vocab_size
        assert lg.shape[1] == 32  # patches + text
    else:
        assert lg.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(lg)))


@pytest.mark.parametrize("arch", sorted(SMOKES))
def test_decode_matches_prefill(arch):
    """Greedy decode logits must equal full-sequence forward logits —
    the KV-cache/state machinery is exact. Three consecutive steps
    through programs that take the cache donated, as ``ServeEngine``
    runs them: a row written at the wrong position, or a row the next
    step cannot see, breaks a later step."""
    cfg = SMOKES[arch]
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    B, S, n_dec = 2, 16, 3
    if cfg.family == "audio":
        toks = jax.random.randint(KEY, (B, cfg.n_codebooks, S + n_dec), 0,
                                  cfg.vocab_size)
    else:
        toks = jax.random.randint(KEY, (B, S + n_dec), 0, cfg.vocab_size)
    batch = {"tokens": toks[..., :S]}
    n_prefix = cfg.n_patches if cfg.family == "vlm" else 0
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_patches, cfg.d_model))
    prefill = jax.jit(model.prefill, donate_argnums=(2,))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    cache = model.init_cache(B, S + n_prefix + 8)
    _, cache = prefill(params, batch, cache)
    lg_dec = []
    for i in range(n_dec):
        lg, cache = decode(
            params, cache, {"tokens": toks[..., S + i:S + i + 1],
                            "cache_index": jnp.asarray(S + n_prefix + i,
                                                       jnp.int32)})
        lg_dec.append(np.asarray(lg[:, 0]))
    # reference: full forward over S + n_dec tokens
    batch_ext = dict(batch, tokens=toks)
    cache2 = model.init_cache(B, S + n_prefix + 8)
    lg_full, _ = jax.jit(model.prefill)(params, batch_ext, cache2)
    for i in range(n_dec):
        np.testing.assert_allclose(
            np.asarray(lg_full[:, n_prefix + S + i]), lg_dec[i],
            rtol=2e-4, atol=2e-4, err_msg=f"decode step {i}")


@pytest.mark.parametrize("arch", sorted(SMOKES))
def test_param_count_formula_matches_init(arch):
    """The analytic count that feeds 6ND model-FLOPs and the NPU cost
    model must equal the real initialized parameter count."""
    cfg = SMOKES[arch]
    model = build_model(cfg)
    params = model.init(KEY)
    real = sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))
    assert real == cfg.param_count(), (
        f"{arch}: analytic {cfg.param_count()} != real {real}")


def test_full_configs_match_published_sizes():
    expected_b = {
        "qwen2-moe-a2.7b": (13.5, 15.0),
        "dbrx-132b": (125.0, 136.0),
        "xlstm-350m": (0.3, 0.55),
        "qwen3-14b": (13.5, 15.5),
        "minicpm-2b": (2.4, 3.0),
        "qwen2-0.5b": (0.4, 0.55),
        "qwen2-72b": (70.0, 75.0),
        "internvl2-1b": (0.4, 0.6),   # LM backbone only (ViT stub)
        "zamba2-7b": (6.0, 7.6),
        "musicgen-large": (1.5, 2.6),
    }
    for arch, (lo, hi) in expected_b.items():
        n = ARCHS[arch].param_count() / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "dbrx-132b"])
@pytest.mark.parametrize("B,S", [(1, 3), (4, 64)])   # 3 and 256 tokens
def test_moe_layer_is_dropless(arch, B, S):
    """Every token's output is its own top-k experts' (gates as the
    config says) plus the shared expert, at any batch and length."""
    import moe_reference
    from repro.models.moe import moe_apply, moe_init

    cfg = SMOKES[arch]
    p = moe_init(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    y, aux, experts = moe_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(moe_reference.moe(cfg, p, x)),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) > 0
    assert 1 <= int(experts) <= cfg.n_experts
