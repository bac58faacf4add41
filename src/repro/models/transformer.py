"""Decoder-only transformer stack (families: dense, moe, vlm, audio).

Scan-over-layers with stacked params; optional remat for training.
Supports three entry points used by the launch layer:

* ``train_logits``: full-sequence forward (causal), returns logits.
* ``prefill``: forward + returns a filled KV cache.
* ``decode_step``: one token (B, 1) against the KV cache.

VLM: precomputed patch embeddings (frontend stub) are prepended to
the text embeddings. Audio: K codebook streams are embedded and
summed per frame; the head emits K logit sets.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import moe_apply, moe_init

Params = Dict[str, Any]


# ----------------------------------------------------------------------
def _layer_init(cfg: ModelConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {
        "attn_norm": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(cfg, k1, dtype),
        "mlp_norm": L.rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_init(cfg, k2, dtype)
    else:
        p["mlp"] = L.mlp_init(cfg, k2, dtype)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 3)
    layer_params = jax.vmap(lambda k: _layer_init(cfg, k, dtype))(
        jnp.stack(keys[: cfg.n_layers]))
    n_streams = max(cfg.n_codebooks, 1)
    V = cfg.vocab_padded
    if cfg.family == "audio":
        embed = (jax.random.normal(
            keys[-1], (n_streams, V, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)
    else:
        embed = (jax.random.normal(
            keys[-1], (V, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)
    p: Params = {
        "embed": embed,
        "layers": layer_params,
        "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        if cfg.family == "audio":
            p["lm_head"] = (jax.random.normal(
                keys[-2], (cfg.d_model, n_streams * V),
                jnp.float32) * 0.02).astype(dtype)
        else:
            p["lm_head"] = L._dense_init(keys[-2], cfg.d_model, V, dtype)
    return p


# ----------------------------------------------------------------------
def _embed_tokens(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    if cfg.family == "audio":
        # tokens: (B, K, S) -> sum of per-codebook embeddings
        parts = [jnp.take(p["embed"][k], tokens[:, k], axis=0)
                 for k in range(cfg.n_codebooks)]
        return sum(parts)
    return jnp.take(p["embed"], tokens, axis=0)


def _unembed(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = p["embed"].T
    else:
        w = p["lm_head"]
    logits = x @ w.astype(x.dtype)
    if cfg.family == "audio":
        B, S = x.shape[0], x.shape[1]
        logits = logits.reshape(B, S, cfg.n_codebooks, cfg.vocab_padded)
    if cfg.vocab_padded != cfg.vocab_size:
        # padded slots never win softmax/sampling
        ids = jnp.arange(cfg.vocab_padded)
        logits = jnp.where(ids < cfg.vocab_size, logits, -1e30)
    return logits


def _layer_apply(
    cfg: ModelConfig, p_l: Params, x: jax.Array, positions: jax.Array,
    cache_l: Optional[Tuple[jax.Array, jax.Array]],
    cache_index: Optional[jax.Array],
    use_flash: bool,
    experts: Optional[Params] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array], jax.Array,
           Optional[jax.Array]]:
    """(x, the layer's new K/V rows, aux loss, experts routed to: MoE
    layers only). ``cache_l`` is only read (decode); the caller writes
    the rows. ``experts``: every layer's routed experts, stacked, for an
    MoE layer whose ``p_l["moe"]["layer"]`` says which is its own;
    without it an MoE layer's experts are its own slice in
    ``p_l["moe"]``."""
    h = L.rmsnorm(p_l["attn_norm"], x, cfg.norm_eps)
    attn_out, rows = L.attention_rows(
        p_l["attn"], cfg, h, positions, cache=cache_l,
        cache_index=cache_index, use_flash=use_flash)
    x = x + attn_out
    h = L.rmsnorm(p_l["mlp_norm"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    routed = None
    if cfg.family == "moe":
        m, layer = p_l["moe"], None
        if experts is not None:
            m = dict(m, **experts)
            layer = m.pop("layer")
        out, aux, routed = moe_apply(m, cfg, h, layer)
    else:
        out = L.mlp_apply(p_l["mlp"], cfg, h)
    return x + out, rows, aux, routed


# ----------------------------------------------------------------------
def forward(
    cfg: ModelConfig,
    p: Params,
    tokens: jax.Array,
    cache: Optional[Params] = None,
    cache_index: Optional[jax.Array] = None,
    patch_embeds: Optional[jax.Array] = None,
    remat: bool = False,
    use_flash: bool = False,
    last_only: bool = False,
) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """Shared trunk. Returns (logits, new_cache, stats): ``stats["aux"]``
    is the auxiliary loss; an MoE model adds ``stats["experts"]``, (L,)
    int32, how many experts each layer routed at least one row to.

    With a cache, each layer returns only its new K/V rows and one
    update per K and V writes them, all layers at once, at
    ``cache_index`` (decode) or 0 (prefill): a caller that donates the
    cache has it written in place, with no copy of it."""
    x = _embed_tokens(p, cfg, tokens)
    if cfg.family == "vlm" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    B, S = x.shape[0], x.shape[1]
    if cache_index is not None:
        positions = jnp.full((B, 1), cache_index, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)

    layers, stacked = p["layers"], None
    if cfg.family == "moe" and cache is not None:
        # serving: the routed experts stay stacked over the layers,
        # outside the scan, and the expert kernel reads its layer's
        # blocks in place (a scanned slice is a copy of every expert of
        # the layer, each step). Training scans the per-layer slices, so
        # each layer's expert gradient is its own slice.
        moe = dict(layers["moe"], layer=jnp.arange(cfg.n_layers))
        stacked = {k: moe.pop(k) for k in ("w_gate", "w_up", "w_down")}
        layers = dict(layers, moe=moe)

    # decode reads the cache; prefill attends over its own rows
    read = cache if cache_index is not None else None

    def body(carry, xs):
        xc, aux = carry
        p_l, cache_l = xs
        xc, rows, aux_l, routed = _layer_apply(
            cfg, p_l, xc, positions, cache_l, cache_index, use_flash,
            stacked)
        return (xc, aux + aux_l), (rows if cache is not None else None,
                                   routed)

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), (rows, routed) = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), (layers, read))
    new_cache = None
    if cache is not None:
        start = cache_index if cache_index is not None else 0
        new_cache = tuple(
            jax.lax.dynamic_update_slice(c, r.astype(c.dtype),
                                         (0, 0, start, 0, 0))
            for c, r in zip(cache, rows))
    if last_only:
        # serving prefill wants next-token logits only: slicing BEFORE
        # the unembed avoids materializing (B, S, V) logits.
        x = x[:, -1:]
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    logits = _unembed(p, cfg, x)
    stats = {"aux": aux}
    if routed is not None:
        stats["experts"] = routed
    return logits, new_cache, stats


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.d_head)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
