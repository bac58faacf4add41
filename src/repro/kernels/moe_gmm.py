"""Ragged grouped matmul — the MoE expert FFN.

``gmm(x, w, group_sizes, layer)``: ``x`` is (M, d) with its rows sorted
by group, group ``g`` holding the next ``group_sizes[g]`` rows; ``w`` is
(L, E, d, f), every layer's experts; the result is (M, f), row ``r`` of
group ``g`` being ``x[r] @ w[layer, g]``. Every row is computed: nothing
is dropped for capacity. The kernel takes the whole stack and the layer
index, and reads its layer's blocks in place: a layer's (E, d, f) slice
handed to a custom call inside the layer loop is a copy of every expert
of the layer, made each step.

The rows are cut into tiles of ``tm``. The grid walks (n-block, visit,
k-block), where a visit is one (row tile, group) pair whose rows
overlap: a tile that spans several groups is visited once for each,
and an expert with no rows has no visit, so its weights are never
read. Visits are ordered by group and then by tile, so one output tile
is only revisited by consecutive steps, and consecutive visits of one
group keep its weight block resident. The group offsets, each visit's
group and row tile, and the number of visits are computed by XLA and
prefetched to scalar memory; the number of visits sizes the grid.

Each visit multiplies the whole row tile (f32 accumulation in VMEM
across k-blocks) and stores only the rows of its group. Weight blocks
are as large as fits ``W_BLOCK_BYTES``: the whole (d, f) matrix of an
expert where it fits, so each expert's weights stream in one DMA.

Validated against ``ref.ragged_matmul_ref`` in interpret mode on the
CPU, and compiled for Mosaic on the TPU (``chip_smoke.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "moe_gmm"              # the kernel's ops in a device trace
W_BLOCK_BYTES = 6 * 2**20     # one weight block; double-buffered in VMEM
VMEM_LIMIT_BYTES = 64 * 2**20


def _divisors(n: int) -> Tuple[int, ...]:
    """Block sizes along a dim of ``n``: ``n`` itself, then the
    multiples of 128 that divide it, largest first."""
    return (n,) + tuple(c for c in range(n - n % 128, 0, -128)
                        if c != n and n % c == 0)


def weight_block(d: int, f: int, itemsize: int) -> Tuple[int, int]:
    """(tk, tn): the whole contraction if any n-block fits beside it,
    and the widest n-block that fits; else the deepest k-block."""
    for tk in _divisors(d):
        for tn in _divisors(f):
            if tk * tn * itemsize <= W_BLOCK_BYTES:
                return tk, tn
    return _divisors(d)[-1], _divisors(f)[-1]


def metadata(group_sizes: jax.Array, m: int, tm: int):
    """(offsets (E+1,), group of each visit, row tile of each visit,
    number of visits) for ``m`` rows (a multiple of ``tm``)."""
    e = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    # every tile but a group's first is its own, so the visits are at
    # most the tiles plus one shared tile per group boundary
    n_visits = tiles_m + e - 1
    gids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), tiles,
                      total_repeat_length=n_visits)
    vstart = jnp.cumsum(tiles) - tiles
    tids = first[gids] + jnp.arange(n_visits, dtype=jnp.int32) - vstart[gids]
    tids = jnp.clip(tids, 0, tiles_m - 1)
    return offsets, gids, tids, jnp.sum(tiles).astype(jnp.int32)


def _kernel(layer, offsets, gids, tids, x_ref, w_ref, o_ref, acc_ref, *,
            tm: int, nk: int):
    v, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        g = gids[v]
        row = tids[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
        layer: jax.Array, *, tm: int, interpret: bool = False) -> jax.Array:
    """x (M, d) sorted by group, M a multiple of ``tm``; w (L, E, d, f);
    group_sizes (E,) int32 with sum at most M; layer: int32 scalar ->
    (M, f). Rows past the last group are left unwritten."""
    m, d = x.shape
    f = w.shape[-1]
    assert m % tm == 0, (m, tm)
    tk, tn = weight_block(d, f, w.dtype.itemsize)
    offsets, gids, tids, n_visits = metadata(group_sizes, m, tm)
    kernel = functools.partial(_kernel, tm=tm, nk=d // tk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(f // tn, n_visits, d // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ni, v, ki, l, o, g, t: (t[v], ki)),
                pl.BlockSpec((None, None, tk, tn),
                             lambda ni, v, ki, l, o, g, t: (l[0], g[v], ki,
                                                            ni)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, v, ki, l, o, g, t: (t[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), offsets, gids, tids, x, w)
