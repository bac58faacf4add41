"""Compile the main path for a described TPU v5e chip: the Pallas
kernels at real widths and the full-width qwen2-0.5b serving steps.

Nothing runs and no chip is needed: the TPU compiler checks tiling,
the kernels' fast-memory budget and device memory for a chip that is
described, not attached. A compile that passes is not a chip run
(``chip_smoke.py`` is). The topology is described inside a fixture, so
that only the worker that runs this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm
from repro.models.registry import build_model
from repro.serve.engine import ServeEngine

V5E_HBM_BYTES = 16 * 1024**3
SERVE_BATCH, SERVE_PROMPT, SERVE_MAX_SEQ = 4, 128, 512
MOE_ROWS, MOE_ROW_TILE = 16 * 512 * 4, 256    # a (16, 512) prefill, top-4
CHAT_BATCH, CHAT_PROMPT, CHAT_MAX_SEQ = 32, 512, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_for_v5e(one_chip, dtype):
    cfg = ARCHS["qwen2-0.5b"]
    b, s, hd = SERVE_BATCH, SERVE_MAX_SEQ, cfg.d_head
    q = _sds((b * cfg.n_heads, s, hd), dtype, one_chip)
    kv = _sds((b * cfg.n_kv_heads, s, hd), dtype, one_chip)
    kernel = functools.partial(
        fa.flash_attention_bhsd, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, causal=True, interpret=False)
    compiled = jax.jit(kernel).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_grouped_matmul_compiles_for_v5e(one_chip):
    """The ragged kernel at Qwen1.5-MoE's widths, gate/up and down, on
    the 6-layer stack of experts the served programs hand it."""
    cfg = ARCHS["qwen2-moe-a2.7b"]
    sizes = _sds((cfg.n_experts,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    kernel = functools.partial(moe_gmm.gmm, tm=MOE_ROW_TILE, interpret=False)
    for d, f in ((cfg.d_model, cfg.d_expert), (cfg.d_expert, cfg.d_model)):
        x = _sds((MOE_ROWS, d), jnp.bfloat16, one_chip)
        w = _sds((6, cfg.n_experts, d, f), jnp.bfloat16, one_chip)
        compiled = jax.jit(kernel).lower(x, w, sizes, layer).compile()
        assert f"{moe_gmm.NAME}" in compiled.as_text()
        assert "tpu_custom_call" in compiled.as_text()
        assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_qwen2_serve_step_fits_one_v5e(one_chip, step):
    """The bf16 ServeEngine programs at published widths fit one chip."""
    cfg = ARCHS["qwen2-0.5b"]
    model = build_model(cfg, remat=False)
    bf16 = jnp.bfloat16

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: model.init(k, bf16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(SERVE_BATCH, SERVE_MAX_SEQ, bf16)))
    if step == "prefill":
        batch = {"tokens": _sds((SERVE_BATCH, SERVE_PROMPT), jnp.int32,
                                one_chip)}
        lowered = jax.jit(model.prefill).lower(params, batch, cache)
    else:
        batch = {"tokens": _sds((SERVE_BATCH, 1), jnp.int32, one_chip),
                 "cache_index": _sds((), jnp.int32, one_chip)}
        lowered = jax.jit(model.decode_step).lower(params, cache, batch)
    compiled = lowered.compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_engine_steps_write_the_donated_cache_in_place(one_chip, step):
    """The engine's own jitted programs, at qwen2-0.5b's widths and the
    chat shapes: the cache comes back in its donated input's buffers,
    and a decode step keeps no temporary as large as one layer's K
    cache, so no whole-cache or whole-layer copy is left."""
    cfg = ARCHS["qwen2-0.5b"]
    bf16 = jnp.bfloat16

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    model = build_model(cfg, remat=False)
    params = on_chip(jax.eval_shape(
        lambda k: model.init(k, bf16), jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, params=params, max_seq=CHAT_MAX_SEQ, dtype=bf16)
    cache = on_chip(jax.eval_shape(
        lambda: eng.model.init_cache(CHAT_BATCH, CHAT_MAX_SEQ, bf16)))
    if step == "prefill":
        batch = {"tokens": _sds((CHAT_BATCH, CHAT_PROMPT), jnp.int32,
                                one_chip)}
        compiled = eng._prefill.lower(params, batch, cache).compile()
    else:
        batch = {"tokens": _sds((CHAT_BATCH, 1), jnp.int32, one_chip),
                 "cache_index": _sds((), jnp.int32, one_chip)}
        compiled = eng._decode.lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert cache_bytes == 402_653_184
    assert mem.alias_size_in_bytes >= cache_bytes
    if step == "decode":
        layer_k = (CHAT_BATCH * CHAT_MAX_SEQ * cfg.n_kv_heads * cfg.d_head
                   * jnp.dtype(bf16).itemsize)
        assert mem.temp_size_in_bytes < layer_k
