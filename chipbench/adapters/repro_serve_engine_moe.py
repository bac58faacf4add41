"""The system under test for a Qwen2-MoE configuration:
``repro.serve.engine.ServeEngine`` serving the program's ``moe`` family.

Builds the program's ``ModelConfig`` from the configuration file, checks
it against the program's own preset (``program_arch``) on every field
but those the file lists under ``reduced``, hands the benchmark's
weights to the engine in the program's parameter layout (a regrouping
of the same arrays, nothing copied), and serves each call through
``ServeEngine.generate``: one batch of prompts of one length, greedy.
A program that cannot run the configuration as the file states it is
refused before anything is built.
"""
from __future__ import annotations

import dataclasses
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
           "n_kv_heads": "num_key_value_heads",
           "vocab_size": "vocab_size", "qkv_bias": "attention_bias",
           "tie_embeddings": "tie_word_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "n_experts": "num_experts",
           "n_experts_per_tok": "num_experts_per_tok",
           "d_expert": "moe_intermediate_size",
           "d_ff": "moe_intermediate_size",
           "moe_norm_topk": "norm_topk_prob"}
# fields the program derives from several file keys
_DERIVED = {"d_head": ("head_dim", "hidden_size", "num_attention_heads"),
            "n_shared_experts": ("shared_expert_intermediate_size",
                                 "moe_intermediate_size")}


def model_config(cfg: Mapping[str, Any]) -> ModelConfig:
    """The program's config for the file, or an error where the program
    cannot run the configuration as the file states it."""
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"the program's experts are SwiGLU, not "
                         f"{cfg['hidden_act']}")
    if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
        raise ValueError("the program makes every layer sparse")
    d_e, d_s = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    if d_s % d_e:
        raise ValueError(f"the program's shared expert is a multiple of "
                         f"{d_e} wide, not {d_s}")
    kw = {f: cfg[k] for f, k in _FIELDS.items()}
    kw["rope_theta"], kw["norm_eps"] = (float(kw["rope_theta"]),
                                        float(kw["norm_eps"]))
    mc = ModelConfig(name=cfg["program_arch"], family="moe",
                     d_head=cfg.get("head_dim", 0),
                     n_shared_experts=d_s // d_e, shared_expert_gate=True,
                     **kw)
    reduced = {f for f, k in _FIELDS.items() if k in cfg["reduced"]} | {
        f for f, keys in _DERIVED.items() if set(keys) & set(cfg["reduced"])}
    preset = ARCHS[cfg["program_arch"]]
    diff = {f.name: (getattr(preset, f.name), getattr(mc, f.name))
            for f in dataclasses.fields(ModelConfig)
            if f.name != "name" and f.name not in reduced
            and getattr(preset, f.name) != getattr(mc, f.name)}
    if diff:
        raise ValueError(f"program preset {cfg['program_arch']} differs from "
                         f"the configuration file: {diff}")
    return mc


def program_params(w: Dict[str, Any]) -> Dict[str, Any]:
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    moe = {k: lw[k] for k in ("router", "w_gate", "w_up", "w_down")}
    moe["shared"] = {k: lw["shared_" + k] for k in ("w_gate", "w_up",
                                                   "w_down")}
    moe["shared_gate"] = lw["w_shared_gate"]
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "lm_head": w["lm_head"],
            "layers": {"attn_norm": {"scale": lw["attn_norm"]},
                       "attn": attn,
                       "mlp_norm": {"scale": lw["mlp_norm"]}, "moe": moe}}


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
