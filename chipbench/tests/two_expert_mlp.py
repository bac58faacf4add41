"""A second architecture for the tests: no attention, and in each layer
two always-on SwiGLU experts whose outputs are averaged.

It has every name an architecture module gives (see
``chipbench.references``), so the benchmark's weights, counts and check
take it with no change of their own. Its weights have an expert axis
that the dense decoder's have not.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Mapping, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.references.dense_decoder import _mm, _rmsnorm

EXPERTS = 2


def leaf_shapes(cfg: Mapping[str, Any]):
    d, L, F, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    return {"embed": ((V, d), "embed"),
            "final_norm": ((d,), "norm"),
            "layers/norm": ((L, d), "norm"),
            "layers/w_gate": ((L, EXPERTS, d, F), "dense"),
            "layers/w_up": ((L, EXPERTS, d, F), "dense"),
            "layers/w_down": ((L, EXPERTS, F, d), "dense")}


class Dims(NamedTuple):
    eps: float

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Dims":
        return cls(eps=float(cfg["rms_norm_eps"]))


def logits(dims: Dims, weights: Dict[str, Any], tokens: jax.Array,
           fp8: bool = False) -> jax.Array:
    emb = weights["embed"].astype(jnp.float32)
    x = jnp.take(emb, tokens, axis=0)

    def body(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        h = _rmsnorm(x, w["norm"], dims.eps)
        g = _mm("td,edf->etf", h, w["w_gate"], fp8)
        u = _mm("td,edf->etf", h, w["w_up"], fp8)
        y = _mm("etf,efd->td", jax.nn.silu(g) * u, w["w_down"], fp8)
        return x + y / EXPERTS, None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), dims.eps)
    return _mm("td,vd->tv", x, emb, fp8)


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    d_ff: int
    vocab: int
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Shapes":
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   dtype_bytes=jnp.dtype(cfg["serve_dtype"]).itemsize)

    @property
    def _matmul_params(self) -> int:
        return self.layers * EXPERTS * 3 * self.d * self.d_ff

    @property
    def params(self) -> int:
        return (self.vocab * self.d + self._matmul_params
                + self.layers * self.d + self.d)

    kv_bytes_per_token = 0

    def decode_flops(self, batch: int, ctx: int) -> float:
        return 2.0 * batch * (self._matmul_params + self.vocab * self.d)

    def decode_bytes(self, batch: int, ctx: int) -> float:
        return self.params * self.dtype_bytes

    def prefill_flops(self, batch: int, seq: int) -> float:
        return (2.0 * batch * seq * self._matmul_params
                + 2.0 * batch * self.vocab * self.d)

    def prefill_bytes(self, batch: int, seq: int) -> float:
        return self.params * self.dtype_bytes


class System:
    """Serves greedy tokens of this very reference, in float32: the
    check must find no gap."""

    def __init__(self, cfg: Mapping[str, Any], traffic: Mapping[str, Any],
                 weights: Dict[str, Any]) -> None:
        dims = Dims.of(cfg)
        self.weights = weights
        self._next = jax.jit(lambda w, seq: jnp.argmax(
            jax.vmap(lambda s: logits(dims, w, s)[-1])(seq), -1))

    def set_weights(self, weights) -> None:
        self.weights = weights

    def generate(self, prompts, n_new: int):
        seq = jnp.asarray(prompts)
        out = []
        with jax.default_matmul_precision("highest"):
            for _ in range(n_new):
                tok = self._next(self.weights, seq)
                out.append(tok)
                seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        return jax.device_get(jnp.stack(out, axis=1)).astype("int32")
