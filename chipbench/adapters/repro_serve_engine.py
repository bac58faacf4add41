"""The system under test: ``repro.serve.engine.ServeEngine``.

Builds the program's ``ModelConfig`` from the configuration file,
checks it against the program's own preset when the file names one
(``program_arch``), hands the benchmark's weights to the engine in the
program's parameter layout, and serves each call through
``ServeEngine.generate``: one batch of prompts of one length, greedy.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.configs.base import ModelConfig
from repro.serve.engine import ServeEngine

# fields of the program's config that the file fixes, by file key
_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
           "vocab_size": "vocab_size", "qkv_bias": "attention_bias",
           "tie_embeddings": "tie_word_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}


def model_config(cfg: Mapping[str, Any]) -> ModelConfig:
    """The program's config for the file, or an error where the program
    cannot run the configuration as the file states it."""
    L = cfg["num_hidden_layers"]
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"the program's MLP is SwiGLU, not {cfg['hidden_act']}")
    if not (cfg.get("scale_emb", 1.0) == 1.0
            and cfg.get("logit_divisor", 1.0) == 1.0
            and math.isclose(cfg.get("scale_depth", math.sqrt(L)),
                             math.sqrt(L))):
        raise ValueError("the program applies no embedding, residual or "
                         "logit scale")
    kw = {f: cfg.get(k, False) for f, k in _FIELDS.items()}
    kw["rope_theta"], kw["norm_eps"] = (float(kw["rope_theta"]),
                                        float(kw["norm_eps"]))
    mc = ModelConfig(name=cfg.get("program_arch", "chipbench"),
                     family="dense", d_head=cfg.get("head_dim", 0), **kw)
    arch = cfg.get("program_arch")
    if arch is not None:
        preset = ARCHS[arch]
        diff = {f: (getattr(preset, f), getattr(mc, f))
                for f in list(_FIELDS) + ["d_head", "family", "mlp_gated",
                                          "vocab_padded"]
                if getattr(preset, f) != getattr(mc, f)}
        if diff:
            raise ValueError(f"program preset {arch} differs from the "
                             f"configuration file: {diff}")
    return mc


def program_params(w: Dict[str, Any]) -> Dict[str, Any]:
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    p = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
         "layers": {"attn_norm": {"scale": lw["attn_norm"]}, "attn": attn,
                    "mlp_norm": {"scale": lw["mlp_norm"]},
                    "mlp": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    return p


class System:
    def __init__(self, cfg: Mapping[str, Any], traffic: Mapping[str, Any],
                 weights: Dict[str, Any]) -> None:
        if not traffic.get("greedy", True):
            raise ValueError("the check compares greedy tokens only")
        dtype = jnp.dtype(cfg["serve_dtype"])
        mc = model_config(cfg)
        self.engine = ServeEngine(mc, params=program_params(weights),
                                  max_seq=traffic["max_seq"], dtype=dtype)
        want = jax.eval_shape(lambda k: self.engine.model.init(k, dtype),
                              jax.random.key(0))
        if (jax.tree.structure(want) != jax.tree.structure(self.engine.params)
                or [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
                != [(a.shape, a.dtype)
                    for a in jax.tree.leaves(self.engine.params)]):
            raise ValueError("weights do not match the program's layout")

    def set_weights(self, weights: Optional[Dict[str, Any]]) -> None:
        """Serve other weights through the same compiled programs (None
        lets the old ones go)."""
        self.engine.params = None if weights is None else program_params(weights)

    def generate(self, prompts: np.ndarray, n_new: int) -> np.ndarray:
        return self.engine.generate(prompts, n_new).tokens
