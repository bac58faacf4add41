"""Shared substrate layers: RMSNorm, RoPE, GQA attention (with
optional qk-norm / QKV bias / KV cache), gated & plain MLPs.

Conventions
-----------
* Params are plain dict pytrees; every init fn takes an explicit key.
* Activations: (batch, seq, d_model). Attention uses (B, S, H, hd).
* ``dtype`` is the compute/param dtype (fp32 for CPU smoke tests,
  bf16 for the dry-run target); softmax/norm statistics in fp32.
* KV caches: (B, S_max, n_kv, hd) per layer, stacked over layers by
  the stacks. Attention only reads a cache and returns its new K/V
  rows (``attention_rows``); each stack writes them into its cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]


# ----------------------------------------------------------------------
def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * p["scale"].astype(x.dtype)


# ----------------------------------------------------------------------
def rope_freqs(hd: int, theta: float, dtype=jnp.float32) -> jax.Array:
    return (1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
def _dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def attention_init(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    d, dq, dkv = cfg.d_model, cfg.d_q, cfg.d_kv
    p: Params = {
        "wq": _dense_init(ks[0], d, dq, dtype),
        "wk": _dense_init(ks[1], d, dkv, dtype),
        "wv": _dense_init(ks[2], d, dkv, dtype),
        "wo": _dense_init(ks[3], dq, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((dq,), dtype)
        p["bk"] = jnp.zeros((dkv,), dtype)
        p["bv"] = jnp.zeros((dkv,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.d_head, dtype)
        p["k_norm"] = rmsnorm_init(cfg.d_head, dtype)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                  chunk: int = 512, context_parallel: bool = False
                  ) -> jax.Array:
    """Flash-style causal attention: scan over KV blocks with online
    softmax. Pure jnp (lowers on every backend — the dry-run's
    stand-in for the Pallas kernel, same blocking): O(S * chunk)
    working set instead of O(S^2) materialized scores + mask.
    q: (B,S,H,hd), k/v: (B,S,Hkv,hd).

    ``context_parallel``: shard the Q sequence over the `model` mesh
    axis (K/V replicated). For archs whose head count doesn't divide
    the TP axis (qwen3's 40 heads on 16 shards), attention is
    otherwise fully replicated per device; CP cuts the per-device
    score traffic by the axis size.
    """
    B, S, H, hd = q.shape
    if context_parallel:
        from jax.sharding import PartitionSpec as P

        q = jax.lax.with_sharding_constraint(
            q, P(None, "model", None, None))
    Hkv = k.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = jnp.moveaxis(k.reshape(B, nc, chunk, Hkv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nc, chunk, Hkv, hd), 1, 0)
    qg = q.reshape(B, S, Hkv, g, hd)
    q_pos = jnp.arange(S)

    def block(carry, inp):
        m_run, l_run, acc, ci = carry
        k_b, v_b = inp
        s = jnp.einsum("bskgh,btkh->bskgt", qg, k_b).astype(jnp.float32)
        s = s * scale
        k_pos = ci * chunk + jnp.arange(chunk)
        causal = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(causal[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bskgt,btkh->bskgh", p.astype(v_b.dtype), v_b).astype(jnp.float32)
        return (m_new, l_new, acc, ci + 1), None

    m0 = jnp.full((B, S, Hkv, g), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, g), jnp.float32)
    a0 = jnp.zeros((B, S, Hkv, g, hd), jnp.float32)
    (m_f, l_f, acc, _), _ = jax.lax.scan(
        block, (m0, l0, a0, jnp.zeros((), jnp.int32)), (kc, vc))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.astype(q.dtype).reshape(B, S, H, hd)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
          mask: Optional[jax.Array]) -> jax.Array:
    """Reference attention: q (B,S,H,hd), k/v (B,T,Hkv,hd), GQA via
    head-group reshape. fp32 softmax."""
    B, S, H, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    q = q.reshape(B, S, Hkv, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def _sdpa_cached(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 k: jax.Array, v: jax.Array,
                 cache_index: jax.Array) -> jax.Array:
    """One decode step's attention: q (B,1,H,hd) over the cache's
    positions ``< cache_index`` and the step's own row k/v (B,1,Hkv,hd),
    in one fp32 softmax, as ``_sdpa`` over the cache with the row
    written at ``cache_index``; the cache is only read."""
    B, S, H, hd = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, hd)
    old = jnp.einsum("bskgh,btkh->bkgst", qg, k_cache).astype(jnp.float32)
    old = jnp.where(jnp.arange(T) < cache_index, old / math.sqrt(hd), -1e30)
    own = jnp.einsum("bskgh,bskh->bkgs", qg, k).astype(jnp.float32)
    own = own / math.sqrt(hd)
    # softmax over the T old positions and the own row together
    m = jnp.maximum(jnp.max(old, axis=-1), own)
    p_old = jnp.exp(old - m[..., None])
    p_own = jnp.exp(own - m)
    denom = jnp.sum(p_old, axis=-1) + p_own
    w_old = (p_old / denom[..., None]).astype(v.dtype)
    w_own = (p_own / denom).astype(v.dtype)
    out = (jnp.einsum("bkgst,btkh->bskgh", w_old, v_cache,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgs,bskh->bskgh", w_own, v,
                        preferred_element_type=jnp.float32))
    return out.astype(v.dtype).reshape(B, S, H, hd)


def attention_rows(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    use_flash: Any = False,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Causal attention that leaves the cache to its caller: returns
    (out, (k, v)), the K/V rows of ``x``, for the caller to write at
    their positions.

    Without ``cache_index`` (training, prefill) ``x`` attends over its
    own K/V. With it (decode, ``x`` (B, 1, d)) over ``cache``'s
    positions before ``cache_index`` and its own row, so the step reads
    the cache (each (B, S_max, n_kv, hd)) and writes nothing into it.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions)
    if cache_index is not None:
        k_cache, v_cache = cache
        # the row as the cache holds it (fp8 caches round it), and the
        # cache upcast at read; scores/softmax math stays in the
        # compute dtype
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        out = _sdpa_cached(q, k_cache.astype(q.dtype),
                           v_cache.astype(q.dtype), k.astype(q.dtype),
                           v.astype(q.dtype), cache_index)
    elif use_flash == "pallas" and S >= 128:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True)
    elif use_flash and S >= 256:
        out = _sdpa_chunked(q, k, v, context_parallel=(use_flash == "cp"))
    else:
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None, None]
        out = _sdpa(q, k, v, mask)
    out = out.reshape(B, S, cfg.d_q) @ p["wo"]
    return out, (k, v)


# ----------------------------------------------------------------------
def mlp_init(cfg: ModelConfig, key, dtype=jnp.float32,
             d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        return {
            "w_gate": _dense_init(ks[0], d, ff, dtype),
            "w_up": _dense_init(ks[1], d, ff, dtype),
            "w_down": _dense_init(ks[2], ff, d, dtype),
        }
    return {
        "w_up": _dense_init(ks[0], d, ff, dtype),
        "w_down": _dense_init(ks[1], ff, d, dtype),
    }


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.mlp_gated:
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]
