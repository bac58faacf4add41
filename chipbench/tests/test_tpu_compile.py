"""Each cell's prefill and decode programs, compiled for a described
TPU v5e at the cell's full size: the chip's compiler accepts them and
they fit its memory. Nothing runs; ``memory_analysis`` is printed."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]

HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("cell_name", _cells())
def test_cell_programs_fit_one_v5e(cell_name, one_chip):
    from chipbench.adapters.repro_serve_engine import (model_config,
                                                       program_params)
    from chipbench.spec import load_cell
    from chipbench.weights import weight_shapes
    from repro.models.registry import build_model

    cell = load_cell(ROOT, cell_name)
    cfg, tr = cell.config, cell.traffic
    dtype = jnp.dtype(cfg["serve_dtype"])
    model = build_model(model_config(cfg), remat=False)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, program_params(weight_shapes(cfg, dtype)))
    b, max_seq = tr["batch"], tr["max_seq"]
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_cache(b, max_seq, dtype)))
    report = {}
    for s in tr["prompt_lens"]:
        toks = on_chip(jax.ShapeDtypeStruct((b, s), jnp.int32))
        c = jax.jit(model.prefill).lower(params, {"tokens": toks},
                                         cache).compile()
        report[f"prefill_{s}"] = c.memory_analysis()
    step = {"tokens": on_chip(jax.ShapeDtypeStruct((b, 1), jnp.int32)),
            "cache_index": on_chip(jax.ShapeDtypeStruct((), jnp.int32))}
    c = jax.jit(model.decode_step).lower(params, cache, step).compile()
    report["decode_step"] = c.memory_analysis()
    for name, m in report.items():
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{cell_name} {name}: arguments {m.argument_size_in_bytes} "
              f"outputs {m.output_size_in_bytes} temps {m.temp_size_in_bytes} "
              f"aliased {m.alias_size_in_bytes} total {total}")
        assert total < HBM, (name, total)
