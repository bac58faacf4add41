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
from repro.serve import tracing


@dataclass
class GenerationResult:
    """Tokens of one ``generate`` call, and its host-clock times:
    ``prefill_s`` from the prompt's put to the end of the prefill's sync
    (prompt, cache, key and prefill); ``decode_s`` from there to the end
    of the last ``serve.fetch`` (every token on the host; the last decode
    step, whose output is discarded, is left running)."""
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
        # the cache is donated: each program writes its new K/V rows
        # into it in place, and the caller never touches the old one
        self._prefill = jax.jit(self.model.prefill, donate_argnums=(2,))
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        tracing.listen()

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
        audio = cfg.family == "audio"
        shape = np.shape(prompt_tokens)
        B, S = shape[0], shape[-1]
        assert S + n_new <= self.max_seq, "increase max_seq"
        with tracing.charged():
            t0 = time.perf_counter()
            toks = jnp.asarray(prompt_tokens, jnp.int32)
            cache = self.model.init_cache(B, self.max_seq, self.dtype)
            # greedy decoding reads no key, so it makes and splits none
            key = sub = jax.random.PRNGKey(seed) if temperature > 0 else None
            batch: Dict[str, Any] = {"tokens": toks}
            if cfg.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (B, cfg.n_patches, cfg.d_model), jnp.float32)
            logits, cache = self._prefill(self.params, batch, cache)
            logits.block_until_ready()
            prefill_routed, cache = self.model.detach_routed(cache)
            last = logits[:, -1:]               # (B, 1, V) | (B, 1, K, V)
            t_prefill = time.perf_counter()

            # Each token goes to the next decode step as a device array;
            # the host copies it one step behind, once that step is
            # queued, so nothing between a sampler and the next launch
            # waits for the device. Each step's work lies in exactly one
            # step span.
            outs = []
            for i in range(n_new):
                with tracing.span("sample", step=i):
                    if key is not None:
                        key, sub = jax.random.split(key)
                    nxt = self._sample(last, sub, temperature)
                    if audio:
                        nxt = jnp.moveaxis(nxt, -1, 1)      # (B, K, 1)
                    outs.append(nxt)
                with tracing.span("dispatch", step=i):
                    if i == n_new - 1:
                        # the count of the steps whose token is kept
                        routed, cache = self.model.detach_routed(cache)
                    idx = jnp.asarray(S + i, jnp.int32)
                    last, cache = self._decode(
                        self.params, cache,
                        {"tokens": nxt, "cache_index": idx})
                if i:
                    with tracing.span("fetch", step=i - 1):
                        outs[i - 1] = np.asarray(outs[i - 1])
            with tracing.span("fetch", step=n_new - 1):
                outs[-1] = np.asarray(outs[-1])
                if routed is not None:
                    tracing.count_routing(int(prefill_routed), int(routed),
                                          n_new - 1, cfg.n_layers)
                t_fetched = time.perf_counter()
        new = np.concatenate(outs, axis=-1)
        t_decode = t_fetched - t_prefill
        # tokens/s counts generated TIMESTEPS per sequence: an audio
        # model emits K parallel codebook streams per step, which is
        # still one token of audio — new.size would over-count by K
        n_tok = new.shape[0] * new.shape[-1]
        return GenerationResult(
            tokens=new,
            prefill_s=t_prefill - t0,
            decode_s=t_decode,
            tokens_per_s=n_tok / max(t_decode, 1e-9),
        )
