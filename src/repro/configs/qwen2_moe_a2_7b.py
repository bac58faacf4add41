"""Qwen1.5-MoE-A2.7B  [hf:Qwen/Qwen1.5-MoE-A2.7B; hf]

24L d_model=2048 16H (MHA, kv=16) vocab=151936, untied head, QKV bias
per the Qwen1.5 lineage, rms_norm_eps 1e-6, RoPE theta 1e6.
MoE in every layer: a router Linear(2048 -> 60), softmax over the 60
routed SwiGLU experts of width 1408, top-4, gates NOT renormalised
(``norm_topk_prob: false``); plus one shared SwiGLU expert of width
5632 (``shared_expert_intermediate_size``, here 4 x ``d_expert``),
scaled by sigmoid(x @ w_shared_gate), w_shared_gate a Linear(2048 -> 1).

Sharding note: 60 routed experts are NOT divisible by the 16-way
`model` axis -> experts replicated across `model`, expert ffn dim
sharded ("ffn" mode); the shared expert is TP-sharded like a dense MLP.
"""
from repro.configs.base import ModelConfig

ARCH = ModelConfig(
    name="qwen2-moe-a2.7b",
    family="moe",
    n_layers=24,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=151936,
    qkv_bias=True,
    n_experts=60,
    n_experts_per_tok=4,
    n_shared_experts=4,
    d_expert=1408,
    moe_norm_topk=False,
    shared_expert_gate=True,
    moe_shard="ffn",
    rope_theta=1_000_000.0,
)

SMOKE = ModelConfig(
    name="qwen2-moe-a2.7b-smoke",
    family="moe",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab_size=512,
    qkv_bias=True,
    n_experts=6,
    n_experts_per_tok=2,
    n_shared_experts=2,
    d_expert=96,
    moe_norm_topk=False,
    shared_expert_gate=True,
    moe_shard="ffn",
)
