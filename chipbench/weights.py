"""Seeded random weights, in the benchmark's own layout.

The benchmark makes the weights, so the reference never takes anything
the program made. The layout is the architecture's: ``leaf_shapes`` of
the configuration's module under ``chipbench/references/`` gives every
leaf's path (``a/b`` is leaf ``b`` of group ``a``), shape and init kind.
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

from chipbench import references

Weights = Dict[str, Any]

EMBED_STD = 0.02
BIAS_STD = 0.1
NORM_STD = 0.1


def jax_seed(seed: int) -> int:
    """A 32-bit key for JAX from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


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
    out: Weights = {}
    for path, leaf in flat.items():
        group, _, name = path.rpartition("/")
        (out.setdefault(group, {}) if group else out)[name] = leaf
    return out


def _spec(cfg: Mapping[str, Any]
          ) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
    shapes = references.of(cfg).leaf_shapes(cfg)
    return tuple((p, s, k) for p, (s, k) in sorted(shapes.items()))


def make_weights(cfg: Mapping[str, Any], seed: int, dtype=jnp.bfloat16
                 ) -> Weights:
    return _make(jax.random.key(jax_seed(seed)), _spec(cfg),
                 jnp.dtype(dtype))


def weight_shapes(cfg: Mapping[str, Any], dtype=jnp.bfloat16) -> Weights:
    """The same tree as ``make_weights``, as ShapeDtypeStructs."""
    spec = _spec(cfg)
    return jax.eval_shape(
        lambda k: _make(k, spec, jnp.dtype(dtype)), jax.random.key(0))
