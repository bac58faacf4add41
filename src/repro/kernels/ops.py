"""jit'd public wrappers around the Pallas kernels.

On the TPU the kernels lower to Mosaic. On the CPU, where the tests
run, they execute in Pallas interpret mode. Any other backend raises:
no silent fallback hides which device ran them. Padding to block
multiples is handled here so callers pass natural shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run on tpu (Mosaic) or on cpu (interpret "
            f"mode), not on {backend!r}")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bk: int = 128
                    ) -> jax.Array:
    """q: (B, S, H, hd); k/v: (B, S, Hkv, hd) -> (B, S, H, hd)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, -1, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, -1, hd)
    sk = kf.shape[1]
    pad_q = (-s) % bq
    pad_k = (-sk) % bk
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    o = fa.flash_attention_bhsd(
        qf, kf, vf, n_heads=h, n_kv_heads=hkv, causal=causal,
        bq=bq, bk=bk, interpret=_interpret())
    o = o[:, :s]
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


TM = 256     # row tile of the ragged matmul, at most


def _row_tile(m: int) -> int:
    return min(TM, -(-m // 16) * 16)


@jax.jit
def _gmm_fwd(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
             layer: jax.Array) -> jax.Array:
    m = x.shape[0]
    tm = _row_tile(m)
    pad = (-m) % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = moe_gmm.gmm(x, w, group_sizes.astype(jnp.int32), layer, tm=tm,
                      interpret=_interpret())
    return out[:m]


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
        layer: Optional[jax.Array] = None) -> jax.Array:
    """Ragged grouped matmul: x (M, d) sorted by group, group_sizes (E,)
    summing to M, and the experts w (E, d, f), or every layer's
    (L, E, d, f) with the ``layer`` to use -> (M, f). The backward pass
    is ``jax.lax.ragged_dot``'s, the same product."""
    if layer is None:
        w, layer = w[None], 0
    return _gmm(x, w, group_sizes, jnp.asarray(layer, jnp.int32))


@jax.custom_vjp
def _gmm(x, w, group_sizes, layer):
    return _gmm_fwd(x, w, group_sizes, layer)


def _gmm_vjp_fwd(x, w, group_sizes, layer):
    return _gmm_fwd(x, w, group_sizes, layer), (x, w, group_sizes, layer)


def _gmm_vjp_bwd(res, g):
    x, w, group_sizes, layer = res
    _, vjp = jax.vjp(
        lambda x_, w_: jax.lax.ragged_dot(x_, w_[layer], group_sizes), x, w)
    return (*vjp(g.astype(x.dtype)), None, None)


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)
