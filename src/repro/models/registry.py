"""Unified Model API over all families — what the launch / train /
serve layers program against.

  model = build_model(cfg)
  params = model.init(key, dtype)
  loss, metrics = model.loss(params, batch)              # training
  logits, cache = model.prefill(params, batch)           # serving
  logits, cache = model.decode_step(params, cache, batch)
  count, cache = model.detach_routed(cache)  # MoE: experts routed to

Batches are dicts:
  train:   {"tokens": (B,S) | (B,K,S), "targets": same, [patch_embeds]}
  prefill: {"tokens": ...}
  decode:  {"tokens": (B,1)|(B,K,1), "cache_index": ()} + cache pytree
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import stacks, transformer

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy; logits promoted to fp32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


@dataclass
class Model:
    cfg: ModelConfig
    use_flash: Any = False      # False | True (chunked jnp) | "pallas"
    remat: bool = True
    prefill_last_only: bool = False

    # ---------------- init ----------------
    def init(self, key, dtype=jnp.float32) -> Params:
        c = self.cfg
        if c.family in ("dense", "moe", "vlm", "audio"):
            return transformer.init_params(c, key, dtype)
        if c.family == "ssm" and c.xlstm_pattern:
            return stacks.xlstm_init(c, key, dtype)
        if c.family in ("ssm", "hybrid"):
            return stacks.zamba2_init(c, key, dtype)
        raise ValueError(c.family)

    # ---------------- training ----------------
    def logits(self, params: Params, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        c = self.cfg
        tokens = batch["tokens"]
        if c.family in ("dense", "moe", "vlm", "audio"):
            lg, _, stats = transformer.forward(
                c, params, tokens, patch_embeds=batch.get("patch_embeds"),
                remat=self.remat, use_flash=self.use_flash)
            return lg, stats["aux"]
        if c.family == "ssm" and c.xlstm_pattern:
            lg, _, aux = stacks.xlstm_forward(c, params, tokens,
                                              remat=self.remat)
            return lg, aux
        lg, _, aux = stacks.zamba2_forward(c, params, tokens,
                                           remat=self.remat,
                                           use_flash=self.use_flash)
        return lg, aux

    def loss(self, params: Params, batch: Batch) -> Tuple[jax.Array, Dict]:
        c = self.cfg
        lg, aux = self.logits(params, batch)
        targets = batch["targets"]
        if c.family == "vlm":
            # patches are prepended: score only the text positions
            n_p = c.n_patches
            lg = lg[:, n_p:]
        if c.family == "audio":
            # lg: (B,S,K,V); targets (B,K,S)
            tgt = jnp.moveaxis(targets, 1, 2)
            ce = cross_entropy(lg, tgt)
        else:
            ce = cross_entropy(lg, targets)
        total = ce + 0.01 * aux
        return total, {"ce": ce, "aux": aux}

    # ---------------- serving ----------------
    # An MoE model's serving state is (k, v, routed): ``routed`` is an
    # int32 scalar that every prefill and decode step adds its experts
    # routed to (summed over layers) to, so the count stays on the
    # device until a caller takes it (``detach_routed``).
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.float32):
        c = self.cfg
        if c.family == "moe":
            return (*transformer.init_cache(c, batch, max_seq, dtype),
                    jnp.zeros((), jnp.int32))
        if c.family in ("dense", "vlm", "audio"):
            return transformer.init_cache(c, batch, max_seq, dtype)
        if c.family == "ssm" and c.xlstm_pattern:
            return stacks.xlstm_state(c, batch, dtype)
        return stacks.zamba2_state(c, batch, max_seq, dtype)

    def prefill(self, params: Params, batch: Batch, cache) -> Tuple[jax.Array, Any]:
        """Full-sequence forward that fills the cache/state."""
        c = self.cfg
        tokens = batch["tokens"]
        if c.family == "moe":
            lg, kv, stats = transformer.forward(
                c, params, tokens, cache=cache[:2],
                use_flash=self.use_flash, last_only=self.prefill_last_only)
            return lg, (*kv, cache[2] + jnp.sum(stats["experts"]))
        if c.family in ("dense", "vlm", "audio"):
            lg, cache, _ = transformer.forward(
                c, params, tokens, cache=cache,
                patch_embeds=batch.get("patch_embeds"),
                use_flash=self.use_flash,
                last_only=self.prefill_last_only)
            return lg, cache
        if c.family == "ssm" and c.xlstm_pattern:
            lg, st, _ = stacks.xlstm_forward(c, params, tokens, state=cache)
            return lg, st
        lg, st, _ = stacks.zamba2_forward(c, params, tokens, state=cache,
                                          use_flash=self.use_flash)
        return lg, st

    def decode_step(self, params: Params, cache, batch: Batch
                    ) -> Tuple[jax.Array, Any]:
        """One new token against the cache. tokens: (B,1) / (B,K,1)."""
        c = self.cfg
        tokens = batch["tokens"]
        idx = batch["cache_index"]
        if c.family == "moe":
            lg, kv, stats = transformer.forward(
                c, params, tokens, cache=cache[:2], cache_index=idx)
            return lg, (*kv, cache[2] + jnp.sum(stats["experts"]))
        if c.family in ("dense", "vlm", "audio"):
            lg, cache, _ = transformer.forward(
                c, params, tokens, cache=cache, cache_index=idx)
            return lg, cache
        if c.family == "ssm" and c.xlstm_pattern:
            lg, st, _ = stacks.xlstm_forward(c, params, tokens, state=cache,
                                             decode=True)
            return lg, st
        lg, st, _ = stacks.zamba2_forward(c, params, tokens, state=cache,
                                          cache_index=idx, decode=True)
        return lg, st

    def detach_routed(self, cache) -> Tuple[Optional[jax.Array], Any]:
        """(experts routed to since ``init_cache`` or the last detach,
        summed over layers and runs, as a device scalar; the state with
        its counter restarted at zero). The count stays the caller's to
        read once the state is donated to a later step, which deletes
        its buffers. (None, cache) for a model without routing."""
        if self.cfg.family != "moe":
            return None, cache
        return cache[2], (*cache[:2], np.zeros((), np.int32))

    # ---------------- shape builders (dry-run / data pipeline) -------
    def batch_shapes(self, kind: str, batch: int, seq: int
                     ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """{name: (shape, dtype)} for every model input of a step."""
        c = self.cfg
        tok = jnp.int32
        if c.family == "audio":
            tok_shape = (batch, c.n_codebooks, seq)
        else:
            tok_shape = (batch, seq)
        if kind == "train":
            d: Dict[str, Tuple[Tuple[int, ...], Any]] = {
                "tokens": (tok_shape, tok),
                "targets": (tok_shape, tok),
            }
            if c.family == "vlm":
                # patches occupy the first n_patches positions of seq
                text = seq - c.n_patches
                d["tokens"] = ((batch, text), tok)
                d["targets"] = ((batch, text), tok)
                d["patch_embeds"] = ((batch, c.n_patches, c.d_model),
                                     jnp.bfloat16)
            return d
        if kind == "prefill":
            d = {"tokens": (tok_shape, tok)}
            if c.family == "vlm":
                text = seq - c.n_patches
                d["tokens"] = ((batch, text), tok)
                d["patch_embeds"] = ((batch, c.n_patches, c.d_model),
                                     jnp.bfloat16)
            return d
        if kind == "decode":
            one = ((batch, c.n_codebooks, 1) if c.family == "audio"
                   else (batch, 1))
            return {"tokens": (one, tok), "cache_index": ((), jnp.int32)}
        raise ValueError(kind)


def build_model(cfg: ModelConfig, use_flash: Any = False,
                remat: bool = True,
                prefill_last_only: bool = False) -> Model:
    return Model(cfg=cfg, use_flash=use_flash, remat=remat,
                 prefill_last_only=prefill_last_only)
