"""Non-transformer stacks: xLSTM (ssm family) and Zamba2 (hybrid).

xLSTM: the layer pattern ``([m]*k_m + [s]*k_s) * reps`` is scanned as
``reps`` segments with inner scans over the stacked mLSTM / sLSTM
params — compile size stays O(1) in depth.

Zamba2: one scan over all Mamba2 layers; the SHARED attention+MLP
block (single param set) is applied via ``lax.cond`` after every
``hybrid_attn_every``-th layer, with its per-application KV cache
carried as a stacked buffer and indexed dynamically.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm

Params = Dict[str, Any]


# ======================================================================
# xLSTM
# ======================================================================
def parse_xlstm_pattern(cfg: ModelConfig) -> Tuple[int, int, int]:
    pat = list(cfg.xlstm_pattern)
    assert pat and pat[0] == "m", "pattern must start with mLSTM blocks"
    k_m = pat.index("s") if "s" in pat else len(pat)
    k_s = 0
    for c in pat[k_m:]:
        if c != "s":
            break
        k_s += 1
    seg = ["m"] * k_m + ["s"] * k_s
    reps, rem = divmod(len(pat), len(seg))
    if rem or pat != seg * reps:
        raise ValueError(f"irregular xLSTM pattern {pat}")
    return k_m, k_s, reps


def xlstm_init(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    k_m, k_s, reps = parse_xlstm_pattern(cfg)
    keys = jax.random.split(key, 4)

    def stack(init_fn, k, outer, inner):
        if inner == 0:
            return None
        flat = jax.random.split(k, outer * inner)
        return jax.vmap(jax.vmap(lambda kk: init_fn(cfg, kk, dtype)))(
            flat.reshape(outer, inner, *flat.shape[1:]))

    p: Params = {
        "embed": (jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype),
        "mlstm": stack(ssm.mlstm_init, keys[1], reps, k_m),
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
    }
    if k_s:
        p["slstm"] = stack(ssm.slstm_init, keys[2], reps, k_s)
    if not cfg.tie_embeddings:
        p["lm_head"] = L._dense_init(keys[3], cfg.d_model, cfg.vocab_size,
                                     dtype)
    return p


def xlstm_state(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> Params:
    k_m, k_s, reps = parse_xlstm_pattern(cfg)

    def rep_stack(state_fn, inner):
        one = state_fn(cfg, batch, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a, (reps, inner) + a.shape).copy(), one)

    st: Params = {"mlstm": rep_stack(ssm.mlstm_state, k_m)}
    if k_s:
        st["slstm"] = rep_stack(ssm.slstm_state, k_s)
    return st


def xlstm_forward(
    cfg: ModelConfig, p: Params, tokens: jax.Array,
    state: Optional[Params] = None, decode: bool = False,
    remat: bool = False,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    k_m, k_s, reps = parse_xlstm_pattern(cfg)
    x = jnp.take(p["embed"], tokens, axis=0)
    with_state = state is not None

    def m_body(carry, xs):
        if with_state:
            p_l, st_l = xs
            xc, new_st = ssm.mlstm_apply(p_l, cfg, carry, st_l, decode)
            return xc, new_st
        xc, _ = ssm.mlstm_apply(xs, cfg, carry, None, False)
        return xc, None

    def s_body(carry, xs):
        if with_state:
            p_l, st_l = xs
            xc, new_st = ssm.slstm_apply(p_l, cfg, carry, st_l, decode)
            return xc, new_st
        xc, _ = ssm.slstm_apply(xs, cfg, carry, None, False)
        return xc, None

    def seg_body(xc, xs):
        if with_state:
            (pm, sm), ps_st = xs["m"], xs.get("s")
            xc, new_m = jax.lax.scan(m_body, xc, (pm, sm))
            new_s = None
            if k_s:
                ps, ss = ps_st
                xc, new_s = jax.lax.scan(s_body, xc, (ps, ss))
            out = {"m": new_m}
            if k_s:
                out["s"] = new_s
            return xc, out
        xc, _ = jax.lax.scan(m_body, xc, xs["m"])
        if k_s:
            xc, _ = jax.lax.scan(s_body, xc, xs["s"])
        return xc, None

    if with_state:
        xs = {"m": (p["mlstm"], state["mlstm"])}
        if k_s:
            xs["s"] = (p["slstm"], state["slstm"])
    else:
        xs = {"m": p["mlstm"]}
        if k_s:
            xs["s"] = p["slstm"]
    body = jax.checkpoint(seg_body) if remat else seg_body
    x, new_states = jax.lax.scan(body, x, xs)
    new_state = None
    if with_state:
        new_state = {"mlstm": new_states["m"]}
        if k_s:
            new_state["slstm"] = new_states["s"]
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return x @ w.astype(x.dtype), new_state, jnp.zeros((), jnp.float32)


# ======================================================================
# Zamba2 (hybrid)
# ======================================================================
def zamba2_init(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 6)
    mamba = jax.vmap(lambda k: ssm.mamba2_init(cfg, k, dtype))(
        jax.random.split(keys[0], cfg.n_layers))
    p: Params = {
        "embed": (jax.random.normal(
            keys[1], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype),
        "mamba": mamba,
        "shared_attn_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "shared_attn": L.attention_init(cfg, keys[2], dtype),
        "shared_mlp_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "shared_mlp": L.mlp_init(cfg, keys[3], dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": L._dense_init(keys[4], cfg.d_model, cfg.vocab_size, dtype),
    }
    return p


def n_attn_applications(cfg: ModelConfig) -> int:
    return cfg.n_layers // max(cfg.hybrid_attn_every, 1)


def zamba2_state(cfg: ModelConfig, batch: int, max_seq: int,
                 dtype=jnp.float32) -> Params:
    one = ssm.mamba2_state(cfg, batch, dtype)
    mamba = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), one)
    n_attn = n_attn_applications(cfg)
    kv = (cfg.n_kv_heads, cfg.d_head)
    return {
        "mamba": mamba,
        "kv_k": jnp.zeros((n_attn, batch, max_seq) + kv, dtype),
        "kv_v": jnp.zeros((n_attn, batch, max_seq) + kv, dtype),
    }


def zamba2_forward(
    cfg: ModelConfig, p: Params, tokens: jax.Array,
    state: Optional[Params] = None, cache_index: Optional[jax.Array] = None,
    decode: bool = False, remat: bool = False, use_flash: bool = False,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    every = cfg.hybrid_attn_every
    x = jnp.take(p["embed"], tokens, axis=0)
    B, S = x.shape[0], x.shape[1]
    # decode attends over the cache before ``cache_index`` and writes
    # its row there; prefill attends over its own rows, written at 0
    step = cache_index if decode else None
    if step is not None:
        positions = jnp.full((B, 1), step, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    with_state = state is not None

    def shared_block(args):
        xc, kv_k, kv_v, a_idx = args
        h = L.rmsnorm(p["shared_attn_norm"], xc, cfg.norm_eps)
        read = None
        if step is not None:
            read = tuple(jax.lax.dynamic_index_in_dim(c, a_idx, 0,
                                                      keepdims=False)
                         for c in (kv_k, kv_v))
        attn_out, rows = L.attention_rows(
            p["shared_attn"], cfg, h, positions, cache=read,
            cache_index=step, use_flash=use_flash)
        if with_state:
            start = 0 if step is None else step
            kv_k, kv_v = (
                jax.lax.dynamic_update_slice(c, r[None].astype(c.dtype),
                                             (a_idx, 0, start, 0, 0))
                for c, r in zip((kv_k, kv_v), rows))
        xc = xc + attn_out
        h = L.rmsnorm(p["shared_mlp_norm"], xc, cfg.norm_eps)
        xc = xc + L.mlp_apply(p["shared_mlp"], cfg, h)
        return xc, kv_k, kv_v, a_idx

    def body(carry, xs):
        xc, kv_k, kv_v = carry
        if with_state:
            p_l, st_l, idx = xs
            xc, new_st = ssm.mamba2_apply(p_l, cfg, xc, st_l, decode)
        else:
            p_l, idx = xs
            xc, new_st = ssm.mamba2_apply(p_l, cfg, xc, None, False)
        do_attn = (idx + 1) % every == 0
        a_idx = (idx + 1) // every - 1
        xc, kv_k, kv_v, _ = jax.lax.cond(
            do_attn, shared_block, lambda a: a, (xc, kv_k, kv_v, a_idx))
        return (xc, kv_k, kv_v), new_st

    idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if with_state:
        kv_k, kv_v = state["kv_k"], state["kv_v"]
        xs = (p["mamba"], state["mamba"], idxs)
    else:
        kv_k = jnp.zeros((n_attn_applications(cfg), B, 0, cfg.n_kv_heads,
                          cfg.d_head), x.dtype)
        kv_v = kv_k
        xs = (p["mamba"], idxs)
    body_fn = jax.checkpoint(body) if remat else body
    (x, kv_k, kv_v), new_mamba = jax.lax.scan(body_fn, (x, kv_k, kv_v), xs)
    new_state = None
    if with_state:
        new_state = {"mamba": new_mamba, "kv_k": kv_k, "kv_v": kv_v}
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    return x @ p["lm_head"].astype(x.dtype), new_state, jnp.zeros((), jnp.float32)
