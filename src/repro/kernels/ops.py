"""jit'd public wrappers around the Pallas kernels.

On the TPU the kernels lower to Mosaic. On the CPU, where the tests
run, they execute in Pallas interpret mode. Any other backend raises:
no silent fallback hides which device ran them. Padding to block
multiples is handled here so callers pass natural shapes.
"""
from __future__ import annotations

import functools

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


@functools.partial(jax.jit, static_argnames=("bc", "bd", "bf"))
def grouped_matmul(x: jax.Array, w: jax.Array, bc: int = 128,
                   bd: int = 512, bf: int = 128) -> jax.Array:
    """x: (E, C, d) @ w: (E, d, f) -> (E, C, f), padding-safe."""
    e, c, d = x.shape
    f = w.shape[2]
    bc_, bd_, bf_ = min(bc, c), min(bd, d), min(bf, f)
    pc, pd, pf = (-c) % bc_, (-d) % bd_, (-f) % bf_
    if pc or pd:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pd)))
    if pd or pf:
        w = jnp.pad(w, ((0, 0), (0, pd), (0, pf)))
    o = moe_gmm.grouped_matmul(x, w, bc=bc_, bd=bd_, bf=bf_,
                               interpret=_interpret())
    return o[:, :c, :f]
