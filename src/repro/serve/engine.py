"""Serving engine: batched prefill + greedy/temperature decode over
the KV cache, for any zoo architecture.

This is the functional layer (real JAX compute). Multi-tenant NPU
scheduling — the paper's subject — sits above it in vserve.py, which
maps engines onto vNPUs and uses the Neu10 simulator as the timing
model for SLO accounting.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.registry import Model, build_model


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, n_new) | (B, K, n_new)
    prefill_s: float
    decode_s: float
    tokens_per_s: float


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: Optional[Any] = None,
                 max_seq: int = 512, seed: int = 0,
                 dtype=jnp.float32) -> None:
        self.cfg = cfg
        self.model: Model = build_model(cfg, remat=False)
        self.max_seq = max_seq
        self.dtype = dtype
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed), dtype)
        self.params = params
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)

    def _sample(self, logits: jax.Array, key, temperature: float):
        # logits: (B, 1, V) or (B, 1, K, V)
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompt_tokens: np.ndarray, n_new: int,
                 temperature: float = 0.0, seed: int = 0
                 ) -> GenerationResult:
        cfg = self.cfg
        toks = jnp.asarray(prompt_tokens, jnp.int32)
        audio = cfg.family == "audio"
        B = toks.shape[0]
        S = toks.shape[-1]
        assert S + n_new <= self.max_seq, "increase max_seq"
        cache = self.model.init_cache(B, self.max_seq, self.dtype)
        key = jax.random.PRNGKey(seed)

        t0 = time.time()
        batch: Dict[str, Any] = {"tokens": toks}
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (B, cfg.n_patches, cfg.d_model), jnp.float32)
        logits, cache = self._prefill(self.params, batch, cache)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        t0 = time.time()
        outs = []
        last = logits[:, -1:]
        if audio:
            pass  # (B, 1, K, V)
        for i in range(n_new):
            key, sub = jax.random.split(key)
            nxt = self._sample(last, sub, temperature)  # (B,1) | (B,1,K)
            if audio:
                nxt_in = jnp.moveaxis(nxt, -1, 1)       # (B,K,1)
            else:
                nxt_in = nxt
            outs.append(np.asarray(nxt_in))
            idx = jnp.asarray(S + i, jnp.int32)
            last, cache = self._decode(
                self.params, cache, {"tokens": nxt_in, "cache_index": idx})
        t_decode = time.time() - t0
        new = np.concatenate(outs, axis=-1)
        # tokens/s counts generated TIMESTEPS per sequence: an audio
        # model emits K parallel codebook streams per step, which is
        # still one token of audio — new.size would over-count by K
        n_tok = new.shape[0] * new.shape[-1]
        return GenerationResult(
            tokens=new,
            prefill_s=t_prefill,
            decode_s=t_decode,
            tokens_per_s=n_tok / max(t_decode, 1e-9),
        )
