"""Multi-pod dry-run: lower + compile every (architecture x input
shape) on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh,
prove it fits (memory_analysis), and harvest the roofline terms
(cost_analysis + post-SPMD collective bytes).

A compile-only tool on 512 forced host devices: it pins JAX to the CPU,
even where the environment names a TPU platform, so it never takes a
chip, and appends its device-count flag to any ``XLA_FLAGS`` already
set. Both must happen before the first jax import — jax locks the
platform and the device count at first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch dbrx-132b \
      --shape train_4k --mesh multi                           # one cell
  ... --out results/dryrun.jsonl        (resumable: done cells skipped)
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512")))

import argparse
import gc
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_SHAPES, ARCHS, SHAPES_BY_NAME, shape_applicable
from repro.configs.base import ModelConfig, ShapeCell
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    dp_axes,
    named,
    param_specs,
    sanitize_spec,
    shardings_for,
    state_specs,
)
from repro.launch.hlo_analysis import hlo_metrics
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models.registry import build_model
from repro.npu.hw_config import V5E

DTYPE = jnp.bfloat16


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(cfg: ModelConfig, cell: ShapeCell, mesh) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no
    allocation) for every model input of this cell."""
    model = build_model(cfg)
    shapes = model.batch_shapes(cell.kind, cell.global_batch, cell.seq_len)
    specs = batch_specs(cfg, cell.kind, mesh)
    out = {}
    for k, (shp, dt) in shapes.items():
        sh = None
        if k in specs:
            sh = jax.sharding.NamedSharding(
                mesh, sanitize_spec(shp, specs[k], mesh))
        out[k] = _sds(shp, dt, sh)
    return out


def _eval_shapes(fn, *args):
    return jax.eval_shape(fn, *args)


def analyze_cell(
    arch_id: str,
    cfg: ModelConfig,
    cell: ShapeCell,
    mesh,
    mesh_name: str,
    opts: frozenset = frozenset(),
) -> Dict[str, Any]:
    """opts — perf-iteration knobs (see EXPERIMENTS.md §Perf):
      flash        chunked online-softmax attention (no S^2 scores)
      pad_vocab    pad vocab to 256-multiple so it TP-shards
      kv_shard_hd  shard KV cache head-dim when kv-heads don't divide
      last_logit   prefill emits last-position logits only
    """
    t0 = time.time()
    if "pad_vocab" in opts:
        cfg = cfg.replace(pad_vocab=True)
    use_flash: Any = "flash" in opts
    if "flash_cp" in opts:
        use_flash = "cp"   # chunked + context-parallel q
    model = build_model(
        cfg, remat=True,
        use_flash=use_flash,
        prefill_last_only="last_logit" in opts)
    key = jax.random.PRNGKey(0)

    p_shapes = jax.eval_shape(lambda k: model.init(k, DTYPE), key)
    p_spec = param_specs(cfg, p_shapes, mesh)
    p_shard = named(mesh, p_spec)
    params_in = jax.tree_util.tree_map(
        lambda s, sh: _sds(s.shape, s.dtype, sh), p_shapes, p_shard)
    batch_in = input_specs(cfg, cell, mesh)

    n_chips = int(np.prod(list(mesh.shape.values())))
    branch_scale = 1.0
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        branch_scale = 1.0 / cfg.hybrid_attn_every

    # some perf variants use with_sharding_constraint(PartitionSpec)
    # internally, which needs an ambient mesh during tracing
    mesh_ctx = mesh
    if cell.kind == "train":
        st_spec = state_specs(cfg, mesh, p_spec)
        st_shard = named(mesh, st_spec)
        m_in = jax.tree_util.tree_map(
            lambda s, sh: _sds(s.shape, jnp.float32, sh), p_shapes,
            st_shard["m"])
        state_in = {
            "params": params_in,
            "m": m_in,
            "v": m_in,
            "step": _sds((), jnp.int32),
        }
        step = make_train_step(model)
        jitted = jax.jit(step, donate_argnums=(0,))
        with mesh_ctx:
            lowered = jitted.lower(state_in, batch_in)
    elif cell.kind == "prefill":
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(cell.global_batch, cell.seq_len, DTYPE))
        c_shard = shardings_for(
            cache_shapes,
            cache_specs(cfg, cell, mesh, cache_shapes,
                        kv_hd_shard="kv_shard_hd" in opts), mesh)
        cache_in = jax.tree_util.tree_map(
            lambda s, sh: _sds(s.shape, s.dtype, sh), cache_shapes, c_shard)
        step = make_prefill_step(model)
        jitted = jax.jit(step, donate_argnums=(2,))
        with mesh_ctx:
            lowered = jitted.lower(params_in, batch_in, cache_in)
    else:  # decode
        kv_dtype = jnp.float8_e4m3fn if "kv8" in opts else DTYPE
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(cell.global_batch, cell.seq_len,
                                     kv_dtype))
        c_shard = shardings_for(
            cache_shapes,
            cache_specs(cfg, cell, mesh, cache_shapes,
                        kv_hd_shard="kv_shard_hd" in opts), mesh)
        cache_in = jax.tree_util.tree_map(
            lambda s, sh: _sds(s.shape, s.dtype, sh), cache_shapes, c_shard)
        step = make_decode_step(model)
        jitted = jax.jit(step, donate_argnums=(1,))
        with mesh_ctx:
            lowered = jitted.lower(params_in, cache_in, batch_in)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-trip-aware accounting from the partitioned HLO text —
    # XLA's cost_analysis() visits scan bodies once and would
    # under-count layer-scanned models by ~n_layers x.
    colls = hlo_metrics(hlo, branch_scale=branch_scale)

    flops = colls.pop("hlo_flops")
    bytes_accessed = colls.pop("hlo_bytes")
    coll_b = colls["total_weighted_bytes"]

    # compile-target estimates from published v5e peaks, not a
    # measurement of any device
    compute_s = flops / V5E.peak_flops_bf16
    memory_s = bytes_accessed / V5E.hbm_bw
    collective_s = coll_b / V5E.ici_bw

    # model flops (6ND train / 2ND forward) across the whole step
    n_active = cfg.active_param_count()
    tokens = cell.tokens
    model_flops = (6.0 if cell.kind == "train" else 2.0) * n_active * tokens
    model_flops_per_chip = model_flops / n_chips

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    row: Dict[str, Any] = {
        "arch": arch_id,
        "shape": cell.name,
        "mesh": mesh_name,
        "opts": sorted(opts),
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "xla_cost_flops": float(cost.get("flops", 0.0) or 0.0),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        "collective_bytes_per_device": coll_b,
        "compute_term_s": compute_s,
        "memory_term_s": memory_s,
        "collective_term_s": collective_s,
        "dominant": dominant,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops) if flops else 0.0,
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "collectives": {k: v for k, v in colls.items() if v},
    }
    return row


def run(args) -> None:
    done = set()
    if args.out and os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skip"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    opts = frozenset(o for o in (args.opt or "").split(",") if o)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    out_f = open(args.out, "a") if args.out else None
    n_ok = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch_id, cfg in ARCHS.items():
            if args.arch and arch_id != args.arch:
                continue
            for cell in ALL_SHAPES:
                if args.shape and cell.name != args.shape:
                    continue
                if (arch_id, cell.name, mesh_name) in done:
                    continue
                ok, why = shape_applicable(cfg, cell)
                if not ok:
                    row = {"arch": arch_id, "shape": cell.name,
                           "mesh": mesh_name, "status": "skip",
                           "reason": why}
                else:
                    try:
                        row = analyze_cell(arch_id, cfg, cell, mesh,
                                           mesh_name, opts=opts)
                        n_ok += 1
                    except Exception as e:  # record and continue
                        row = {"arch": arch_id, "shape": cell.name,
                               "mesh": mesh_name, "status": "error",
                               "error": f"{type(e).__name__}: {e}",
                               "trace": traceback.format_exc()[-2000:]}
                        n_fail += 1
                msg = (f"[{mesh_name}] {arch_id} x {cell.name}: "
                       f"{row['status']}")
                if row["status"] == "ok":
                    msg += (f" compile={row['compile_s']:.1f}s"
                            f" dom={row['dominant']}"
                            f" flops/dev={row['flops_per_device']:.3e}")
                elif row["status"] == "error":
                    msg += f" ({row['error'][:160]})"
                print(msg, flush=True)
                if out_f:
                    out_f.write(json.dumps(row) + "\n")
                    out_f.flush()
                gc.collect()
    print(f"dry-run finished: {n_ok} ok, {n_fail} failed", flush=True)
    if out_f:
        out_f.close()
    if n_fail:
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCHS) + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES_BY_NAME) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--opt", default="",
                    help="comma list: flash,pad_vocab,kv_shard_hd,last_logit")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    run(args)


if __name__ == "__main__":
    main()
