"""Pallas kernel validation: shape/dtype sweeps + properties against
the pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import gqa_attention_ref, ragged_matmul_ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 128, 4, 64),    # minimal blocks
    (2, 256, 8, 64),    # multi-block
    (1, 384, 8, 128),   # 3 blocks, big head
    (2, 200, 4, 64),    # padding path
])
def test_flash_attention_sweep(shape, dtype):
    B, S, H, hd = shape
    for hkv in {H, max(H // 4, 1)}:
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, S, hkv, hd), dtype)
        v = jax.random.normal(ks[2], (B, S, hkv, hd), dtype)
        out = ops.flash_attention(q, k, v, causal=True)
        ref = gqa_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **_tol(dtype))


def test_flash_attention_causality():
    """Output at position i must not depend on tokens > i."""
    B, S, H, hd = 1, 256, 4, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    out1 = ops.flash_attention(q, k, v, causal=True)
    k2 = k.at[:, S // 2:].set(jax.random.normal(ks[3], (B, S // 2, H, hd)))
    v2 = v.at[:, S // 2:].set(0.0)
    out2 = ops.flash_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, : S // 2]),
                               np.asarray(out2[:, : S // 2]),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_matches_model_sdpa():
    """The kernel agrees with the model-zoo reference attention."""
    from repro.models.layers import _sdpa

    B, S, H, hd = 2, 128, 4, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, 2, hd))
    v = jax.random.normal(ks[2], (B, S, 2, hd))
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None, None]
    ref = _sdpa(q, k, v, mask)
    out = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Mosaic on the TPU, the interpreter on the CPU test platform; any
    other backend raises rather than silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


def _sizes(layout: str, m: int, e: int) -> np.ndarray:
    """Rows of each of ``e`` groups, summing to ``m``."""
    if layout == "one expert":
        out = np.zeros(e, np.int32)
        out[e // 2] = m
        return out
    cuts = np.sort(np.random.default_rng(m).integers(0, m + 1, e - 1))
    out = np.diff(np.r_[0, cuts, m]).astype(np.int32)
    if layout == "empty experts":        # every other group empty
        out[1::2] = 0
        out[0] += m - out.sum()
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout,m,d,f,e", [
    ("padding", 37, 100, 130, 5),       # rows, d and f off every tile
    ("empty experts", 300, 256, 128, 8),
    ("one expert", 64, 128, 96, 6),
    ("decode", 64, 128, 128, 60),       # 64 rows over 60 experts
])
def test_ragged_matmul_sweep(layout, m, d, f, e, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (m, d), dtype)
    w = jax.random.normal(ks[1], (e, d, f), dtype)
    sizes = _sizes(layout, m, e)
    out = ops.gmm(x, w, jnp.asarray(sizes))
    ref = ragged_matmul_ref(x, w, sizes)
    # bf16 outputs of sums of 100-300 unit products round at 2^-8 of ~16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **(dict(rtol=2e-2, atol=0.25) if dtype == jnp.bfloat16
           else dict(rtol=2e-5, atol=2e-5)))


@given(
    e=st.integers(1, 8),
    m=st.integers(1, 80),
    d=st.integers(1, 96),
    f=st.integers(1, 96),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_ragged_matmul_property(e, m, d, f, seed):
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(m, rng.dirichlet(np.ones(e))).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.gmm(x, w, jnp.asarray(sizes))),
        np.asarray(ragged_matmul_ref(x, w, sizes)), rtol=1e-4, atol=1e-4)


def test_an_expert_without_rows_is_never_visited():
    """The grid visits each (row tile, group) pair that shares a row,
    so an empty group's weights are never read."""
    from repro.kernels import moe_gmm

    sizes = np.array([0, 40, 0, 0, 9, 15, 0], np.int32)
    offsets, gids, tids, n = moe_gmm.metadata(jnp.asarray(sizes), 64, 16)
    n = int(n)
    visits = list(zip(np.asarray(gids)[:n].tolist(),
                      np.asarray(tids)[:n].tolist()))
    # group 1: rows 0-39, tiles 0-2; group 4: rows 40-48, tiles 2-3;
    # group 5: rows 49-63, tile 3
    assert visits == [(1, 0), (1, 1), (1, 2), (4, 2), (4, 3), (5, 3)]
    assert np.asarray(offsets).tolist() == [0, 0, 40, 40, 40, 49, 64, 64]


def test_ragged_matmul_gradients_are_ragged_dots():
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (50, 64))
    w = jax.random.normal(ks[1], (4, 64, 32))
    g = jax.random.normal(ks[2], (50, 32))
    sizes = jnp.asarray([20, 0, 25, 5], jnp.int32)

    def loss(fn):
        return lambda x_, w_: jnp.sum(fn(x_, w_, sizes) * g)
    got = jax.grad(loss(ops.gmm), argnums=(0, 1))(x, w)
    want = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
