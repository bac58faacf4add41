"""AdamW, hand-rolled (the project does not depend on optax).

Moments are fp32 regardless of param dtype; the update math runs in
fp32 and casts back, so bf16 training (the dry-run configuration) is
numerically sane. Optionally applies int8 gradient compression with
error feedback before the update — see repro/distributed/compress.py.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Any


def adamw_init(params: Params) -> Dict[str, Params]:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(zeros32, params),
        "v": jax.tree_util.tree_map(zeros32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def adamw_update(
    params: Params,
    grads: Params,
    opt_state: Dict[str, Params],
    lr: jax.Array,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    grad_clip: float = 1.0,
) -> Tuple[Params, Dict[str, Params]]:
    step = opt_state["step"] + 1
    # global-norm clip
    gnorm = jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))

    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g32
        v = b2 * v + (1 - b2) * jnp.square(g32)
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(opt_state["m"])
    flat_v = jax.tree_util.tree_leaves(opt_state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}
