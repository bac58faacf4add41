"""Algorithmic operations and bytes of a dense decoder, from its shapes.

The roofline and utilisation metrics divide by these counts, so they
count what the algorithm needs, never what an implementation does:

* a decode step reads every weight once and the *live* KV context of
  each row (not the cache's full ``max_seq``), and writes one KV entry;
* prefill is causal attention (half the score matrix) and computes
  logits at the last position only;
* an embedding lookup gathers rows and costs no operations; a tied
  table is read once, as the unembedding.

Shapes come from the configuration file's keys (Hugging Face names),
so the counts do not change when the program changes how it computes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Mapping

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``.

    A kind missing from ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


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
            dtype_bytes=DTYPE_BYTES[cfg["serve_dtype"]])

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


def min_seconds(flops: float, nbytes: float, peaks: Mapping[str, Any]
                ) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def call_useful_flops(shapes: Shapes, batch: int, prompt: int,
                      n_new: int) -> float:
    """Operations that generating ``n_new`` tokens after a ``prompt``-long
    prefill needs: the prefill (which yields the first token) and one
    decode step for each further token."""
    total = shapes.prefill_flops(batch, prompt)
    for j in range(n_new - 1):
        total += shapes.decode_flops(batch, prompt + j + 1)
    return total

