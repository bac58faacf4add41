"""Sharding rules: DP over (`pod`,`data`), TP/EP over `model`.

Every rule checks divisibility against the mesh axis size and falls
back (alternate dim, then replicate) — non-divisible cases (e.g.
qwen3's 40 heads or minicpm's 122753 vocab on a 16-way axis) degrade
gracefully instead of failing to lower. The fallbacks taken are
queryable (``explain_params``) and recorded in the dry-run report.

Only params + inputs + caches are constrained; intermediate layouts
are left to GSPMD propagation (and then audited via the roofline HLO
dump).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell

MODEL_AX = "model"


def dp_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if len(axes) > 1 else axes[0]


def _ax(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _div(n: int, k: int) -> bool:
    return n % k == 0


# ----------------------------------------------------------------------
def param_specs(cfg: ModelConfig, params_shapes, mesh: Mesh):
    """PartitionSpec pytree matching the params pytree.

    ``params_shapes``: pytree of ShapeDtypeStruct (from eval_shape).
    """
    M = _ax(mesh, MODEL_AX)
    heads_ok = _div(cfg.n_heads, M)
    kv_ok = _div(cfg.n_kv_heads, M)

    def spec(path, leaf) -> P:
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        name = keys[-1] if keys else ""
        shape = leaf.shape
        nd = len(shape)
        # stacked-layer leading dims (L,) / (reps, k) are never sharded.
        def col(last_ok: bool, row_dim: int = -2) -> P:
            """shard last dim on model (col-parallel) w/ fallbacks."""
            spec = [None] * nd
            if last_ok and _div(shape[-1], M):
                spec[-1] = MODEL_AX
            return P(*spec)

        def row() -> P:
            spec = [None] * nd
            if _div(shape[-2], M):
                spec[-2] = MODEL_AX
            return P(*spec)

        # ---- embeddings / unembeddings ----
        if name == "embed":
            spec = [None] * nd
            if _div(shape[-2], M):          # vocab
                spec[-2] = MODEL_AX
            elif _div(shape[-1], M):        # d_model fallback
                spec[-1] = MODEL_AX
            return P(*spec)
        if name == "lm_head":
            spec = [None] * nd
            if _div(shape[-1], M):
                spec[-1] = MODEL_AX
            elif _div(shape[-2], M):
                spec[-2] = MODEL_AX
            return P(*spec)

        # ---- attention ----
        if cfg.xlstm_pattern and name in ("wq", "wk", "wv"):
            # mLSTM q/k/v: 4 heads never divide the model axis; shard
            # the contraction/feature dim instead (GSPMD inserts the
            # per-chunk psum) — xlstm-350m is tiny, traffic negligible.
            return col(True)
        if name == "wq":
            return col(heads_ok)
        if name in ("wk", "wv"):
            return col(kv_ok)
        if name == "wo":
            sp = [None] * nd
            if heads_ok and _div(shape[-2], M):
                sp[-2] = MODEL_AX
            return P(*sp)
        if name == "bq":
            return col(heads_ok)
        if name in ("bk", "bv"):
            return col(kv_ok)

        # ---- MoE experts: leading (L, E, ...) ----
        if name in ("w_gate", "w_up", "w_down") and nd >= 4:
            expert_mode = cfg.moe_shard == "expert" and _div(cfg.n_experts, M)
            sp = [None] * nd
            if expert_mode:
                sp[-3] = MODEL_AX            # E dim
            elif name in ("w_gate", "w_up") and _div(shape[-1], M):
                sp[-1] = MODEL_AX            # ffn cols
            elif name == "w_down" and _div(shape[-2], M):
                sp[-2] = MODEL_AX            # ffn rows
            return P(*sp)

        # ---- dense / shared-expert MLP ----
        if name in ("w_gate", "w_up"):
            return col(True)
        if name == "w_down":
            return row()

        # ---- mamba2 ----
        if name in ("w_z", "w_x"):
            return col(True)
        if name == "out_proj":
            return row()
        if name in ("conv_w", "conv_b") and cfg.family in ("ssm", "hybrid"):
            return col(True)
        if name in ("A_log", "D", "dt_bias"):
            return col(_div(cfg.ssm_heads or 1, M))
        if name == "gate_norm":
            return col(True)

        # ---- xLSTM ----
        if name == "w_gates":               # sLSTM (up, 4up): aligned splits
            return col(_div(shape[-1], 4 * M))
        # mLSTM w_up (d, 2up): z/u split aligns iff up % shard == 0
        if name == "w_up" and cfg.xlstm_pattern:
            return col(_div(shape[-1], 2 * M))

        # everything else (norms, router, small gates): replicate
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(spec, params_shapes)


def explain_params(cfg: ModelConfig, params_shapes, mesh: Mesh) -> Dict[str, str]:
    """Human-readable {param_path: spec} — used in the dry-run report."""
    specs = param_specs(cfg, params_shapes, mesh)
    out = {}

    def fmt(path, s, leaf):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[key] = f"{tuple(leaf.shape)} -> {s}"

    jax.tree_util.tree_map_with_path(
        lambda pth, s, l: fmt(pth, s, l), specs, params_shapes)
    return out


# ----------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, kind: str, mesh: Mesh) -> Dict[str, P]:
    dp = dp_axes(mesh)
    toks = P(dp, None, None) if cfg.family == "audio" else P(dp, None)
    d: Dict[str, P] = {"tokens": toks}
    if kind == "train":
        d["targets"] = toks
    if cfg.family == "vlm" and kind in ("train", "prefill"):
        d["patch_embeds"] = P(dp, None, None)
    if kind == "decode":
        d["cache_index"] = P()
    return d


def cache_specs(cfg: ModelConfig, cell: ShapeCell, mesh: Mesh, cache_shapes,
                kv_hd_shard: bool = False):
    """Specs for the KV-cache / recurrent-state pytree.

    decode_32k: batch over DP, kv-heads over model (when divisible).
    long_500k (batch=1): SEQUENCE over `data` (KV) and SSM heads over
    `model` — the sub-quadratic long-context layout.
    ``kv_hd_shard``: perf-iteration knob — when the kv-head count
    doesn't divide the model axis (GQA kv=8 on 16 shards), shard the
    HEAD-DIM channels instead (128/16=8); attention contracts hd so
    GSPMD adds a small psum but the cache bytes/device drop 16x.
    """
    M = _ax(mesh, MODEL_AX)
    dp = dp_axes(mesh)
    batch_shardable = _div(cell.global_batch, int(np.prod(
        [mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,))])))
    kv_ok = _div(cfg.n_kv_heads, M)
    ssm_heads_ok = _div(cfg.ssm_heads or 1, M)

    def spec(path, leaf) -> P:
        keys = [str(getattr(k, "key", getattr(k, "name", ""))) for k in path]
        name = keys[-1] if keys else ""
        shape = leaf.shape
        nd = len(shape)
        if name in ("kv_k", "kv_v") or nd == 5 and "mamba" not in keys:
            # attention KV: (L|n_attn, B, S, kv, hd)
            sp = [None] * nd
            if batch_shardable:
                sp[1] = dp
            elif _div(shape[2], 16) and cell.global_batch == 1:
                sp[2] = "data"               # sequence-sharded cache
            if kv_ok:
                sp[3] = MODEL_AX
            elif kv_hd_shard and _div(shape[4], M):
                sp[4] = MODEL_AX
            return P(*sp)
        if name == "ssm":
            # (L, B, nh, st, hd)
            sp = [None] * nd
            if batch_shardable:
                sp[1] = dp
            if ssm_heads_ok:
                sp[2] = MODEL_AX
            return P(*sp)
        if name in ("conv", "conv_bc"):
            sp = [None] * nd
            if batch_shardable:
                sp[1] = dp
            if name == "conv" and _div(shape[-1], M):
                sp[-1] = MODEL_AX
            return P(*sp)
        if name == "C":                      # mLSTM matrix memory
            sp = [None] * nd
            if batch_shardable:
                sp[2] = dp
            return P(*sp)
        if name in ("c", "n", "h"):          # sLSTM
            sp = [None] * nd
            if batch_shardable:
                sp[2] = dp
            return P(*sp)
        # plain transformer tuple-cache leaves: (L, B, S, kv, hd)
        sp = [None] * nd
        if nd == 5:
            if batch_shardable:
                sp[1] = dp
            elif _div(shape[2], 16) and cell.global_batch == 1:
                sp[2] = "data"
            if kv_ok:
                sp[3] = MODEL_AX
        return P(*sp)

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def state_specs(cfg: ModelConfig, mesh: Mesh, param_spec_tree):
    """Train state = {params, m, v, step}: moments follow params."""
    return {
        "params": param_spec_tree,
        "m": param_spec_tree,
        "v": param_spec_tree,
        "step": P(),
    }


def named(mesh: Mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def sanitize_spec(shape: Tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the
    dim — last-resort guard so no input can fail to lower."""
    out = []
    for i, entry in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        k = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(entry if shape[i] % k == 0 else None)
    return P(*out)


def ambient_mesh():
    """The mesh a program is being traced under (``with mesh:`` or
    ``jax.sharding.use_mesh``), or None on a single device: layers that
    must place work across devices themselves, as a Pallas kernel
    needs, ask it."""
    from jax._src import mesh as _mesh_lib

    mesh = _mesh_lib.thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return None
    return mesh


def shardings_for(shapes_tree, spec_tree, mesh: Mesh):
    """Zip a ShapeDtypeStruct tree with a PartitionSpec tree into
    NamedShardings, sanitizing non-divisible entries. (P is a tuple
    subclass, so a plain two-tree tree_map would recurse into it —
    flatten with is_leaf instead.)"""
    flat_shapes, tdef = jax.tree_util.tree_flatten(shapes_tree)
    flat_specs = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    out = [
        NamedSharding(mesh, sanitize_spec(tuple(s.shape), sp, mesh))
        for s, sp in zip(flat_shapes, flat_specs)
    ]
    return jax.tree_util.tree_unflatten(tdef, out)
