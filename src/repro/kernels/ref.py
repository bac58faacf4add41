"""Pure-jnp oracles for the Pallas kernels (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True) -> jax.Array:
    """q: (BH, Sq, hd); k/v: (BH, Sk, hd) — kv already head-matched.
    fp32 softmax, output in q.dtype."""
    _, sq, hd = q.shape
    sk = k.shape[1]
    scores = jnp.einsum("bqh,bkh->bqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask[None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", w.astype(v.dtype), v)


def gqa_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, S, Hkv, hd). Returns (B, S, H, hd)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, -1, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, -1, hd)
    o = attention_ref(qf, kf, vf, causal)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def ragged_matmul_ref(x: jax.Array, w: jax.Array,
                      group_sizes) -> jax.Array:
    """x: (M, d) sorted by group; w: (E, d, f); group_sizes: (E,), known
    on the host -> (M, f), one plain matmul per group in fp32. Rows past
    the last group are zero."""
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    start = 0
    for g, n in enumerate(np.asarray(group_sizes).tolist()):
        rows = x[start:start + n].astype(jnp.float32)
        out = out.at[start:start + n].set(rows @ w[g].astype(jnp.float32))
        start += n
    return out.astype(x.dtype)
