"""Grouped (per-expert) matmul kernel — the MoE FFN hot-spot.

x: (E, C, d) dispatched token slots, w: (E, d, f) expert weights.
Grid (e, ci, fi, di) with the reduction (d) innermost; an fp32
accumulator tile lives in VMEM scratch across d-blocks. Block shapes
are MXU-aligned (128 multiples); the (bc x bd) x (bd x bf) working
set stays within a VMEM budget of a few MB.

Validated against ``ref.grouped_matmul_ref`` in interpret mode on the
CPU, and compiled for Mosaic on the TPU (``chip_smoke.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)          # (bc, bd)
    w = w_ref[0].astype(jnp.float32)          # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _final():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,       # (E, C, d)
    w: jax.Array,       # (E, d, f)
    *,
    bc: int = 128,
    bd: int = 512,
    bf: int = 128,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    f = w.shape[2]
    bc = min(bc, c)
    bd = min(bd, d)
    bf = min(bf, f)
    assert c % bc == 0 and d % bd == 0 and f % bf == 0, (c, d, f, bc, bd, bf)
    grid = (e, c // bc, f // bf, d // bd)
    kernel = functools.partial(_gmm_kernel, nd=d // bd)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e_, ci, fi, di: (e_, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda e_, ci, fi, di: (e_, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda e_, ci, fi, di: (e_, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(x, w)
