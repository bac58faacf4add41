"""Pallas kernel validation: shape/dtype sweeps + properties against
the pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import gqa_attention_ref, grouped_matmul_ref

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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (4, 128, 256, 128),
    (2, 64, 512, 96),    # padding on f
    (6, 100, 300, 130),  # padding everywhere
])
def test_grouped_matmul_sweep(shape, dtype):
    E, C, d, f = shape
    x = jax.random.normal(KEY, (E, C, d), dtype)
    w = jax.random.normal(KEY, (E, d, f), dtype)
    out = ops.grouped_matmul(x, w)
    ref = grouped_matmul_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


@given(
    e=st.integers(1, 4),
    c=st.integers(1, 64),
    d=st.integers(1, 192),
    f=st.integers(1, 96),
)
@settings(max_examples=15, deadline=None)
def test_grouped_matmul_property(e, c, d, f):
    x = jax.random.normal(KEY, (e, c, d))
    w = jax.random.normal(KEY, (e, d, f))
    out = ops.grouped_matmul(x, w)
    ref = grouped_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
