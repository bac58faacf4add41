"""Plain float32 reference of the MoE decoder, in the program's parameter
layout (``transformer.init_params`` of a ``moe`` config).

One full forward pass over whole sequences: no cache, no kernels, no
sorting. Every expert runs on every token, and its output is weighted
by the token's softmax probability of it where the expert is in the
token's top-k (ties to the lower index, as ``lax.top_k``; renormalised
over the top-k only where the config says so) and by zero elsewhere. The shared expert is scaled by
sigmoid(x @ shared_gate) where the config has that gate. Every matmul
is at ``Precision.HIGHEST``. Nothing of ``repro.models`` or
``repro.kernels`` is imported.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, T, heads, hd), position t at index t."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, a, x):
    B, T, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = _mm("btd,de->bte", x, a["wq"]) + a.get("bq", 0.0)
    k = _mm("btd,de->bte", x, a["wk"]) + a.get("bk", 0.0)
    v = _mm("btd,de->bte", x, a["wv"]) + a.get("bv", 0.0)
    q = _rope(q.reshape(B, T, H, hd), cfg.rope_theta)
    k = jnp.repeat(_rope(k.reshape(B, T, Hkv, hd), cfg.rope_theta),
                   H // Hkv, axis=2)
    v = jnp.repeat(v.reshape(B, T, Hkv, hd), H // Hkv, axis=2)
    s = _mm("bthd,bshd->bhts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhts,bshd->bthd", p, v).reshape(B, T, H * hd)
    return _mm("bte,ed->btd", o, a["wo"])


def _swiglu(x, w_gate, w_up, w_down):
    return _mm("...f,fd->...d",
               jax.nn.silu(_mm("...d,df->...f", x, w_gate))
               * _mm("...d,df->...f", x, w_up), w_down)


def moe(cfg, m, x):
    """x: (..., d) -> (..., d)."""
    probs = jax.nn.softmax(_mm("...d,de->...e", x, m["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.n_experts_per_tok)
    if cfg.moe_norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, cfg.n_experts) * top[..., None],
                      axis=-2)
    every = jax.nn.silu(_mm("...d,edf->...ef", x, m["w_gate"])) * _mm(
        "...d,edf->...ef", x, m["w_up"])
    every = _mm("...ef,efd->...ed", every, m["w_down"])
    y = jnp.einsum("...e,...ed->...d", weights, every)
    if "shared" in m:
        s = _swiglu(x, m["shared"]["w_gate"], m["shared"]["w_up"],
                    m["shared"]["w_down"])
        if cfg.shared_expert_gate:
            s = s * jax.nn.sigmoid(_mm("...d,do->...o", x, m["shared_gate"]))
        y = y + s
    return y


def logits(cfg, params, tokens):
    """Next-token logits (B, T, V), float32, of token ids (B, T)."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.take(p["embed"], tokens, axis=0)

    def layer(x, w):
        x = x + _attention(cfg, w["attn"],
                           _rmsnorm(x, w["attn_norm"]["scale"], cfg.norm_eps))
        h = _rmsnorm(x, w["mlp_norm"]["scale"], cfg.norm_eps)
        return x + moe(cfg, w["moe"], h), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rmsnorm(x, p["final_norm"]["scale"], cfg.norm_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return _mm("btd,dv->btv", x, head)
