"""Flash attention for the TPU MXU (pl.pallas_call + BlockSpec VMEM
tiling), causal + GQA.

Tiling: grid = (batch*heads, Sq/bq, Sk/bk) with the K index innermost
so the online-softmax running stats (m, l) and the fp32 accumulator
live in VMEM scratch across K blocks. Block shapes are 128-aligned to
the 128x128 systolic array — the same tiles the NeuISA compiler uses
as μTOp granularity (DESIGN.md §3).

GQA is handled in the index maps: query head h reads KV head
h // (H // Hkv); KV blocks are never materialized per-Q-head.

Validated against ``ref.attention_ref`` in interpret mode on the CPU,
and compiled for Mosaic on the TPU (``chip_smoke.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref,
                  *, bq: int, bk: int, nk: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)          # (bk, hd)
    v = v_ref[0].astype(jnp.float32)          # (bk, hd)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_ref[...]                        # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ki == nk - 1)
    def _final():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,       # (BH, Sq, hd)   flattened batch*q-heads
    k: jax.Array,       # (BKV, Sk, hd)  flattened batch*kv-heads
    v: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    nq, nk = sq // bq, sk // bk
    group = n_heads // n_kv_heads
    scale = 1.0 / math.sqrt(hd)

    def kv_head(bh_idx):
        b = bh_idx // n_heads
        h = bh_idx % n_heads
        return b * n_kv_heads + h // group

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (kv_head(b), j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (kv_head(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
