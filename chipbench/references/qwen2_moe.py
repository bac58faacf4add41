"""The Qwen2-MoE decoder (Qwen1.5-MoE-A2.7B): its weight layout, its plain
float32 forward pass, and its operations and bytes.

Blocks as the dense decoder's (``dense_decoder.py``: RMSNorm, RoPE,
attention with q/k/v bias), with the MLP replaced by a sparse MoE block
as Hugging Face's ``Qwen2MoeSparseMoeBlock`` computes it: router logits
``h @ router`` (``num_experts`` of them), softmax, the top
``num_experts_per_tok`` probabilities as gates (divided by their sum
only where ``norm_topk_prob``), each chosen expert a SwiGLU of width
``moe_intermediate_size``; plus a shared SwiGLU expert of width
``shared_expert_intermediate_size`` scaled by
``sigmoid(h @ w_shared_gate)``. Every layer is sparse
(``decoder_sparse_step`` 1, no ``mlp_only_layers``); the head is untied.

The reference computes every expert for every token and weights each by
its gate, zero outside the token's top-k: no sorting, no capacity, so it
drops nothing. One sequence, every matmul at ``Precision.HIGHEST``, the
layers in a scan that upcasts one layer's weights at a time. ``fp8=True``
rounds both operands of every matmul, the router's included, to
float8_e4m3fn (``dense_decoder``'s control). Nothing of the program
under test is imported.

``Shapes`` counts what the algorithm needs. A token needs attention, the
router, its top-k experts, the shared expert and the head; a step reads
the weights of the experts its tokens were routed to, which depends on
routing, so the bytes take ``experts``, the mean number of experts
routed to per layer, as an argument: the program's routing counter
supplies it, and no count here guesses it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from chipbench.references.dense_decoder import _mm, _rmsnorm, _rope


def leaf_shapes(cfg: Mapping[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Path -> (shape, init kind) of every leaf, ``L`` layers stacked on
    the leading axis and the ``E`` experts on the next::

        embed (V, d); final_norm (d,); lm_head (d, V)
        layers: attn_norm (L, d), wq (L, d, H*hd), wk/wv (L, d, Hkv*hd),
                bq/bk/bv (L, ...) when attention_bias, wo (L, H*hd, d),
                mlp_norm (L, d), router (L, d, E),
                w_gate/w_up (L, E, d, F), w_down (L, E, F, d),
                shared_w_gate/shared_w_up (L, d, Fs), shared_w_down
                (L, Fs, d), w_shared_gate (L, d, 1)
    """
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    Fs = cfg["shared_expert_intermediate_size"]
    hd = cfg.get("head_dim") or d // H
    dq, dkv = H * hd, Hkv * hd
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "lm_head": ((d, V), "dense"),
        "layers/attn_norm": ((L, d), "norm"),
        "layers/wq": ((L, d, dq), "dense"),
        "layers/wk": ((L, d, dkv), "dense"),
        "layers/wv": ((L, d, dkv), "dense"),
        "layers/wo": ((L, dq, d), "dense"),
        "layers/mlp_norm": ((L, d), "norm"),
        "layers/router": ((L, d, E), "dense"),
        "layers/w_gate": ((L, E, d, F), "dense"),
        "layers/w_up": ((L, E, d, F), "dense"),
        "layers/w_down": ((L, E, F, d), "dense"),
        "layers/shared_w_gate": ((L, d, Fs), "dense"),
        "layers/shared_w_up": ((L, d, Fs), "dense"),
        "layers/shared_w_down": ((L, Fs, d), "dense"),
        "layers/w_shared_gate": ((L, d, 1), "dense"),
    }
    if cfg.get("attention_bias"):
        # N(0, 0.02^2), the "embed" kind: at the "bias" kind's 0.1, the
        # biases put one direction into every token's residual stream,
        # and a B=16 decode step routes to about 29 experts a layer, not
        # the 40 of a balanced router
        out.update({"layers/bq": ((L, dq), "embed"),
                    "layers/bk": ((L, dkv), "embed"),
                    "layers/bv": ((L, dkv), "embed")})
    return out


class Dims(NamedTuple):
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    norm_topk: bool
    eps: float
    rope_theta: float

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], heads=h,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            norm_topk=bool(cfg["norm_topk_prob"]),
            eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]))


def _swiglu(x, w_gate, w_up, w_down, fp8):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", x, w_gate, fp8))
               * _mm("td,df->tf", x, w_up, fp8), w_down, fp8)


def _block(dims: Dims, fp8: bool, x: jax.Array, w: Dict[str, jax.Array]
           ) -> jax.Array:
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    T = x.shape[0]
    H, Hkv, hd = dims.heads, dims.kv_heads, dims.head_dim
    h = _rmsnorm(x, w["attn_norm"], dims.eps)
    q = _mm("td,de->te", h, w["wq"], fp8)
    k = _mm("td,de->te", h, w["wk"], fp8)
    v = _mm("td,de->te", h, w["wv"], fp8)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(T, H, hd), dims.rope_theta)
    k = jnp.repeat(_rope(k.reshape(T, Hkv, hd), dims.rope_theta),
                   H // Hkv, axis=1)
    v = jnp.repeat(v.reshape(T, Hkv, hd), H // Hkv, axis=1)
    s = _mm("thd,shd->hts", q, k, fp8) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hts,shd->thd", p, v, fp8).reshape(T, H * hd)
    x = x + _mm("te,ed->td", o, w["wo"], fp8)

    h = _rmsnorm(x, w["mlp_norm"], dims.eps)
    probs = jax.nn.softmax(_mm("td,de->te", h, w["router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, dims.experts) * top[..., None], -2)
    every = (jax.nn.silu(_mm("td,edf->tef", h, w["w_gate"], fp8))
             * _mm("td,edf->tef", h, w["w_up"], fp8))
    every = _mm("tef,efd->ted", every, w["w_down"], fp8)
    routed = jnp.einsum("te,ted->td", gates, every, precision="highest")
    shared = _swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                     w["shared_w_down"], fp8)
    shared = shared * jax.nn.sigmoid(
        _mm("td,do->to", h, w["w_shared_gate"], fp8))
    return x + routed + shared


def logits(dims: Dims, weights: Dict[str, Any], tokens: jax.Array,
           fp8: bool = False) -> jax.Array:
    """Next-token logits (T, V) in float32 at every position of one
    sequence ``tokens`` (T,)."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, w_l):
        return _block(dims, fp8, x, w_l), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), dims.eps)
    return _mm("td,dv->tv", x, weights["lm_head"].astype(jnp.float32), fp8)


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    d_expert: int
    d_shared: int
    vocab: int
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Shapes":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=h,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            d_shared=cfg["shared_expert_intermediate_size"],
            vocab=cfg["vocab_size"],
            qkv_bias=bool(cfg.get("attention_bias", False)),
            dtype_bytes=jnp.dtype(cfg["serve_dtype"]).itemsize)

    # ---- parameters ----------------------------------------------------
    @property
    def expert_params(self) -> int:
        """One routed expert: gate, up and down."""
        return 3 * self.d * self.d_expert

    @property
    def layer_dense_matmul_params(self) -> int:
        """The matmuls every token of a layer runs but the routed experts':
        attention, the router, the shared expert and its gate."""
        dq, dkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return (self.d * (dq + 2 * dkv) + dq * self.d
                + self.d * self.experts + 3 * self.d * self.d_shared + self.d)

    @property
    def layer_dense_params(self) -> int:
        """Every parameter of a layer but the routed experts'."""
        dq, dkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        bias = dq + 2 * dkv if self.qkv_bias else 0
        return self.layer_dense_matmul_params + bias + 2 * self.d

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d

    @property
    def params(self) -> int:
        return (2 * self.embed_params + self.d + self.layers
                * (self.layer_dense_params + self.experts * self.expert_params))

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    def _weight_bytes_read(self, experts: float) -> float:
        """Bytes of the weights one forward pass reads: every layer's but
        the routed experts', ``experts`` experts a layer, the final norm
        and the head."""
        return (self.layers * (self.layer_dense_params
                               + experts * self.expert_params)
                + self.d + self.embed_params) * self.dtype_bytes

    def _token_flops(self) -> float:
        """Matmul operations of one token through every layer and the
        head, attention scores aside."""
        return 2.0 * (self.layers * (self.layer_dense_matmul_params
                                     + self.top_k * self.expert_params)
                      + self.embed_params)

    def _attn_flops(self, q_rows: int, keys: float) -> float:
        return 4.0 * self.layers * self.heads * self.head_dim * q_rows * keys

    # ---- one decode step -------------------------------------------------
    def decode_flops(self, batch: int, ctx: int) -> float:
        """One new token per row, attending over ``ctx`` positions."""
        return batch * self._token_flops() + self._attn_flops(batch, ctx)

    def decode_bytes(self, batch: int, ctx: int, experts: float) -> float:
        """``experts``: mean experts routed to per layer in the step."""
        return (self._weight_bytes_read(experts)
                + batch * ctx * self.kv_bytes_per_token
                + batch * self.d * self.dtype_bytes)

    # ---- prefill -------------------------------------------------------
    def prefill_flops(self, batch: int, seq: int) -> float:
        """Causal attention, logits at the last position only."""
        tokens = batch * seq
        head = 2.0 * self.embed_params
        return (tokens * (self._token_flops() - head)
                + self._attn_flops(tokens, (seq + 1) / 2.0) + batch * head)

    def prefill_bytes(self, batch: int, seq: int, experts: float) -> float:
        tokens = batch * seq
        return (self._weight_bytes_read(experts)
                + tokens * self.kv_bytes_per_token
                + tokens * self.d * self.dtype_bytes)

    # ---- the routed experts of one layer (the ragged matmul) ------------
    def gmm_flops(self, rows: int) -> float:
        """Gate, up and down of ``rows`` (token, expert) rows."""
        return 2.0 * rows * self.expert_params

    def gmm_bytes(self, rows: int, experts: float) -> float:
        """The weights of ``experts`` experts, and each of the three
        matmuls reading its rows and writing its result once."""
        act = 3 * rows * (self.d + self.d_expert)
        return (experts * self.expert_params + act) * self.dtype_bytes
