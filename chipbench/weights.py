"""Seeded random weights of a dense decoder, in the benchmark's own layout.

The benchmark makes the weights, so the reference never takes anything
the program made. Layout (``L`` layers stacked on the leading axis)::

    embed (V, d); final_norm (d,); lm_head (d, V) when untied
    layers: attn_norm (L, d), wq (L, d, H*hd), wk/wv (L, d, Hkv*hd),
            bq/bk/bv (L, ...) when attention_bias, wo (L, H*hd, d),
            mlp_norm (L, d), w_gate/w_up (L, d, F), w_down (L, F, d)

All leaves are made on the device by one jitted call, directly in the
served dtype.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Weights = Dict[str, Any]

EMBED_STD = 0.02
BIAS_STD = 0.1
NORM_STD = 0.1


def jax_seed(seed: int) -> int:
    """A 32-bit key for JAX from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def leaf_shapes(cfg: Mapping[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Path -> (shape, init kind) of every leaf."""
    d, L, F, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    dq, dkv = H * hd, Hkv * hd
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "layers/attn_norm": ((L, d), "norm"),
        "layers/wq": ((L, d, dq), "dense"),
        "layers/wk": ((L, d, dkv), "dense"),
        "layers/wv": ((L, d, dkv), "dense"),
        "layers/wo": ((L, dq, d), "dense"),
        "layers/mlp_norm": ((L, d), "norm"),
        "layers/w_gate": ((L, d, F), "dense"),
        "layers/w_up": ((L, d, F), "dense"),
        "layers/w_down": ((L, F, d), "dense"),
    }
    if cfg.get("attention_bias"):
        out.update({"layers/bq": ((L, dq), "bias"),
                    "layers/bk": ((L, dkv), "bias"),
                    "layers/bv": ((L, dkv), "bias")})
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "dense")
    return out


def _leaf(key, shape, kind: str, dtype):
    z = jax.random.normal(key, shape, dtype)
    if kind == "dense":
        return z * jnp.asarray(1.0 / math.sqrt(shape[-2]), dtype)
    if kind == "embed":
        return z * jnp.asarray(EMBED_STD, dtype)
    if kind == "bias":
        return z * jnp.asarray(BIAS_STD, dtype)
    return jnp.asarray(1.0, dtype) + z * jnp.asarray(NORM_STD, dtype)


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: Tuple[Tuple[str, Tuple[int, ...], str], ...], dtype):
    keys = jax.random.split(key, len(spec))
    flat = {path: _leaf(k, shape, kind, dtype)
            for k, (path, shape, kind) in zip(keys, spec)}
    out: Weights = {"layers": {}}
    for path, leaf in flat.items():
        if path.startswith("layers/"):
            out["layers"][path.split("/", 1)[1]] = leaf
        else:
            out[path] = leaf
    return out


def make_weights(cfg: Mapping[str, Any], seed: int, dtype=jnp.bfloat16
                 ) -> Weights:
    spec = tuple((p, s, k) for p, (s, k) in sorted(leaf_shapes(cfg).items()))
    return _make(jax.random.key(jax_seed(seed)), spec, jnp.dtype(dtype))


def weight_shapes(cfg: Mapping[str, Any], dtype=jnp.bfloat16) -> Weights:
    """The same tree as ``make_weights``, as ShapeDtypeStructs."""
    spec = tuple((p, s, k) for p, (s, k) in sorted(leaf_shapes(cfg).items()))
    return jax.eval_shape(
        lambda k: _make(k, spec, jnp.dtype(dtype)), jax.random.key(0))
