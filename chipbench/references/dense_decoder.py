"""The dense decoder-only transformer: its weight layout, its plain
float32 forward pass, and its operations and bytes.

Llama-style blocks as Qwen2 and MiniCPM publish them: RMSNorm before
attention and before the MLP, rotary position embedding (rotate-half,
``rope_theta``), grouped-query attention with an optional q/k/v bias,
a SwiGLU MLP, and a tied or separate output head. MiniCPM's scalars
are read from the configuration: ``scale_emb`` multiplies the
embeddings, ``scale_depth / sqrt(num_hidden_layers)`` each residual
branch, and ``logit_divisor`` divides the last hidden state.

No cache, no batching, no kernels: one sequence, every matmul at
``Precision.HIGHEST``, the layers in a scan that upcasts one layer's
weights at a time so the whole float32 model never sits in memory.
Nothing of the program under test is imported.

``fp8=True`` rounds both operands of every matmul to float8_e4m3fn
(per-tensor scale to the format's largest value) before multiplying in
float32: the lower-precision control that the check must reject.

``Shapes`` counts what the algorithm needs, never what an implementation
does, so the roofline and utilisation metrics that divide by it do not
change when the program changes how it computes:

* a decode step reads every weight once and the *live* KV context of
  each row (not the cache's full ``max_seq``), and writes one KV entry;
* prefill is causal attention (half the score matrix) and computes
  logits at the last position only;
* an embedding lookup gathers rows and costs no operations; a tied
  table is read once, as the unembedding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def leaf_shapes(cfg: Mapping[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Path -> (shape, init kind) of every leaf, ``L`` layers stacked on
    the leading axis::

        embed (V, d); final_norm (d,); lm_head (d, V) when untied
        layers: attn_norm (L, d), wq (L, d, H*hd), wk/wv (L, d, Hkv*hd),
                bq/bk/bv (L, ...) when attention_bias, wo (L, H*hd, d),
                mlp_norm (L, d), w_gate/w_up (L, d, F), w_down (L, F, d)
    """
    d, L, F, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    dq, dkv = H * hd, Hkv * hd
    out = {
        "embed": ((V, d), "embed"),
        "final_norm": ((d,), "norm"),
        "layers/attn_norm": ((L, d), "norm"),
        "layers/wq": ((L, d, dq), "dense"),
        "layers/wk": ((L, d, dkv), "dense"),
        "layers/wv": ((L, d, dkv), "dense"),
        "layers/wo": ((L, dq, d), "dense"),
        "layers/mlp_norm": ((L, d), "norm"),
        "layers/w_gate": ((L, d, F), "dense"),
        "layers/w_up": ((L, d, F), "dense"),
        "layers/w_down": ((L, F, d), "dense"),
    }
    if cfg.get("attention_bias"):
        out.update({"layers/bq": ((L, dq), "bias"),
                    "layers/bk": ((L, dkv), "bias"),
                    "layers/bv": ((L, dkv), "bias")})
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), "dense")
    return out


class Dims(NamedTuple):
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    rope_theta: float
    scale_emb: float
    residual_scale: float
    logit_divisor: float
    tied: bool

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Dims":
        d, h, L = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_hidden_layers"])
        depth = cfg.get("scale_depth")
        return cls(
            layers=L, heads=h, kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            scale_emb=float(cfg.get("scale_emb", 1.0)),
            residual_scale=1.0 if depth is None else depth / math.sqrt(L),
            logit_divisor=float(cfg.get("logit_divisor", 1.0)),
            tied=bool(cfg["tie_word_embeddings"]))


def _round_fp8(x: jax.Array) -> jax.Array:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec: str, a: jax.Array, b: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (T, heads, head_dim), position t at row t."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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
    k = _rope(k.reshape(T, Hkv, hd), dims.rope_theta)
    v = v.reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)     # query head i uses kv head i // g
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = _mm("thd,shd->hts", q, k, fp8) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hts,shd->thd", p, v, fp8).reshape(T, H * hd)
    x = x + dims.residual_scale * _mm("te,ed->td", o, w["wo"], fp8)
    h = _rmsnorm(x, w["mlp_norm"], dims.eps)
    g = _mm("td,df->tf", h, w["w_gate"], fp8)
    u = _mm("td,df->tf", h, w["w_up"], fp8)
    return x + dims.residual_scale * _mm(
        "tf,fd->td", jax.nn.silu(g) * u, w["w_down"], fp8)


def logits(dims: Dims, weights: Dict[str, Any], tokens: jax.Array,
           fp8: bool = False) -> jax.Array:
    """Next-token logits (T, V) in float32 at every position of one
    sequence ``tokens`` (T,)."""
    emb = weights["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.float32) * dims.scale_emb

    def body(x, w_l):
        return _block(dims, fp8, x, w_l), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rmsnorm(x, weights["final_norm"].astype(jnp.float32), dims.eps)
    x = x / dims.logit_divisor
    head = (emb.T if dims.tied else weights["lm_head"]).astype(jnp.float32)
    return _mm("td,dv->tv", x, head, fp8)


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: Mapping[str, Any]) -> "Shapes":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=h,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // h,
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            tied=bool(cfg["tie_word_embeddings"]),
            qkv_bias=bool(cfg.get("attention_bias", False)),
            dtype_bytes=jnp.dtype(cfg["serve_dtype"]).itemsize)

    # ---- parameters ----------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        dq, dkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.d * (dq + 2 * dkv) + dq * self.d + 3 * self.d * self.d_ff

    @property
    def layer_params(self) -> int:
        dq, dkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        bias = dq + 2 * dkv if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d

    @property
    def params(self) -> int:
        head = 0 if self.tied else self.embed_params
        return (self.embed_params + head + self.layers * self.layer_params
                + self.d)

    @property
    def kv_bytes_per_token(self) -> int:
        return (self.layers * 2 * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    @property
    def _weight_bytes_read(self) -> int:
        """Bytes of the weights one forward pass reads: every layer, the
        final norm, and the unembedding table (the tied table once)."""
        return (self.layers * self.layer_params + self.d
                + self.embed_params) * self.dtype_bytes

    def _attn_flops(self, q_rows: int, keys: float) -> float:
        # QK^T and PV: 2 matmuls of 2 * head_dim ops per (query, key, head)
        return 4.0 * self.layers * self.heads * self.head_dim * q_rows * keys

    # ---- one decode step -------------------------------------------------
    def decode_flops(self, batch: int, ctx: int) -> float:
        """One new token per row, attending over ``ctx`` positions (the
        new one included)."""
        per_row = 2.0 * (self.layers * self.layer_matmul_params
                         + self.embed_params)
        return batch * per_row + self._attn_flops(batch, ctx)

    def decode_bytes(self, batch: int, ctx: int) -> float:
        return (self._weight_bytes_read
                + batch * ctx * self.kv_bytes_per_token
                + batch * self.d * self.dtype_bytes)

    # ---- prefill -------------------------------------------------------
    def prefill_flops(self, batch: int, seq: int) -> float:
        tokens = batch * seq
        causal_keys = (seq + 1) / 2.0      # mean keys per query, causal
        return (2.0 * tokens * self.layers * self.layer_matmul_params
                + self._attn_flops(tokens, causal_keys)
                + 2.0 * batch * self.d * self.vocab)

    def prefill_bytes(self, batch: int, seq: int) -> float:
        tokens = batch * seq
        return (self._weight_bytes_read
                + tokens * self.kv_bytes_per_token
                + tokens * self.d * self.dtype_bytes)
