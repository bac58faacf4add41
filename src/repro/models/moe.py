"""Mixture-of-Experts layer: dropless top-k routing over a ragged
grouped matmul.

Every token's top-k (token, expert) assignments are sorted by expert
and run through ``kernels.ops.gmm`` (gate/up, then down), whose groups
are the experts' row counts: every assignment is computed at any batch
and length, and an expert that got no rows reads no weights. The rows
are then unsorted and combined with their gates. The gates are the
softmax probabilities of the chosen experts, renormalised to sum to one
only where ``cfg.moe_norm_topk``. A shared expert (``n_shared_experts``
x ``d_expert`` wide) is added to every token, scaled by
``sigmoid(x @ shared_gate)`` where ``cfg.shared_expert_gate``.
Differentiable through the gates and the matmuls (the kernel's backward
pass is ``jax.lax.ragged_dot``'s).

Under a mesh the expert FFN runs in a ``shard_map``, since a Pallas
kernel is not partitioned for it: each data shard sorts its own tokens,
and the experts are split over ``model`` as ``cfg.moe_shard`` and
``distributed.sharding.param_specs`` lay them out, by expert or by ffn
column; the shards' partial sums are added with one ``psum``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import MODEL_AX, ambient_mesh, dp_axes
from repro.kernels import ops
from repro.models.layers import _dense_init, mlp_apply, mlp_init

Params = Dict[str, Any]


def moe_init(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    d_e = cfg.d_expert or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 6)

    def experts(k, d_in, d_out):
        return (jax.random.normal(k, (E, d_in, d_out), jnp.float32)
                * (1.0 / math.sqrt(d_in))).astype(dtype)

    p: Params = {
        "router": _dense_init(ks[0], d, E, dtype),
        "w_gate": experts(ks[1], d, d_e),
        "w_up": experts(ks[2], d, d_e),
        "w_down": experts(ks[3], d_e, d),
    }
    if cfg.n_shared_experts:
        shared_cfg = cfg.replace(mlp_gated=True)
        p["shared"] = mlp_init(shared_cfg, ks[4], dtype,
                               d_ff=cfg.n_shared_experts * d_e)
        if cfg.shared_expert_gate:
            p["shared_gate"] = _dense_init(ks[5], d, 1, dtype)
    return p


def group_sizes(experts: jax.Array, n: int) -> jax.Array:
    """Rows of each of ``n`` experts among the assignments ``experts``."""
    return jnp.sum(experts[None, :] == jnp.arange(n)[:, None], axis=1,
                   dtype=jnp.int32)


def _expert_ffn(xt: jax.Array, gates: jax.Array, idx: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                layer: Optional[jax.Array], n_experts: int,
                axis: Optional[str] = None) -> jax.Array:
    """Each token's chosen experts' SwiGLU outputs, summed with their
    gates: (T, d) float32. ``axis``: inside a ``shard_map``, the mesh
    axis the experts are split over; the result is then this shard's
    part of the sum, added over ``axis`` here."""
    T, k = idx.shape
    m = T * k
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = group_sizes(flat, n_experts)
    src = order // k                                         # token per row
    back = jnp.argsort(order)                                # row per pick
    local = w_gate.shape[-3]
    if local < n_experts:
        # this shard holds experts [first, first + local): rotate their
        # rows to the front, where the kernel's groups start
        first = jax.lax.axis_index(axis) * local
        start = jnp.sum(jnp.where(jnp.arange(n_experts) < first, sizes, 0))
        sizes = jax.lax.dynamic_slice(sizes, (first,), (local,))
        src = jnp.take(src, (jnp.arange(m) + start) % m)
        back = (back - start) % m
    xs = jnp.take(xt, src, axis=0)                           # (T*k, d)
    h = (jax.nn.silu(ops.gmm(xs, w_gate, sizes, layer))
         * ops.gmm(xs, w_up, sizes, layer))
    ys = ops.gmm(h, w_down, sizes, layer)                    # sorted rows
    if local < n_experts:      # rows of other shards' experts: unwritten
        ys = jnp.where((jnp.arange(m) < jnp.sum(sizes))[:, None], ys, 0)
    y = jnp.take(ys, back, axis=0).reshape(T, k, -1)
    y = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), gates)
    return y if axis is None else jax.lax.psum(y, axis)


def _sharded_expert_ffn(mesh, cfg: ModelConfig, xt, gates, idx, w_gate,
                        w_up, w_down, layer):
    """``_expert_ffn`` in a ``shard_map`` over ``mesh``: tokens split
    over the data axes, the experts over ``model`` as the parameters
    are laid out (``param_specs``)."""
    names = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in names)
    n_dp = math.prod(mesh.shape[a] for a in dp)
    tok = P(dp_axes(mesh) if dp and xt.shape[0] % n_dp == 0 else None, None)
    n_model = mesh.shape[MODEL_AX] if MODEL_AX in names else 1
    lead = (None,) * (w_gate.ndim - 3)           # the layer axis, if stacked
    f = w_gate.shape[-1]
    if n_model > 1 and cfg.moe_shard == "expert" \
            and cfg.n_experts % n_model == 0:
        axis = MODEL_AX
        wg = wd = P(*lead, MODEL_AX, None, None)
    elif n_model > 1 and f % n_model == 0:
        axis = MODEL_AX
        wg, wd = P(*lead, None, None, MODEL_AX), P(*lead, None, MODEL_AX, None)
    else:
        axis = None
        wg = wd = P(*lead, None, None, None)
    fn = functools.partial(_expert_ffn, n_experts=cfg.n_experts, axis=axis)
    args = (xt, gates, idx, w_gate, w_up, w_down)
    specs = (tok, tok, tok, wg, wg, wd)
    if layer is None:
        fn = functools.partial(fn, layer=None)
    else:
        args, specs = args + (layer,), specs + (P(),)
    return jax.shard_map(fn, mesh=mesh, in_specs=specs, out_specs=tok,
                         check_vma=False)(*args)


def moe_apply(p: Params, cfg: ModelConfig, x: jax.Array,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output, aux_loss, experts that got at least one row).
    x: (B, S, d). The routed experts are this layer's (E, d, f), or with
    ``layer`` every layer's (L, E, d, f), stacked."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    xt = x.reshape(B * S, d)
    probs = jax.nn.softmax((xt @ p["router"]).astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                     # (T, k)
    if cfg.moe_norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # load-balancing auxiliary loss (Switch-style), by scatter-add: a
    # (T, E) one-hot is O(T * E) bytes
    density = jnp.zeros((E,), jnp.float32).at[idx[:, 0]].add(1.0)
    aux = E * jnp.sum(density / (B * S) * jnp.mean(probs, axis=0))
    routed = jnp.sum(group_sizes(idx.reshape(-1), E) > 0).astype(jnp.int32)

    experts = (p["w_gate"], p["w_up"], p["w_down"], layer)
    mesh = ambient_mesh()
    if mesh is None:
        y = _expert_ffn(xt, gates, idx, *experts, n_experts=E)
    else:
        y = _sharded_expert_ffn(mesh, cfg, xt, gates, idx, *experts)

    if "shared" in p:
        s = mlp_apply(p["shared"], cfg.replace(mlp_gated=True), xt)
        if cfg.shared_expert_gate:
            s = s * jax.nn.sigmoid(xt @ p["shared_gate"])
        y = y + s.astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux, routed
