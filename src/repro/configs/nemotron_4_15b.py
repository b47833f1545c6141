"""nemotron-4-15b — dense; GQA, squared-ReLU MLP, LayerNorm, partial rotary.

Shapes: arXiv:2402.16819 (Nemotron-4 15B Technical Report), Table 1 — 32
layers, d_model 6144, 48 query / 8 KV heads, 256k vocabulary, squared-ReLU
MLP, untied embeddings, no biases, RoPE. The FFN width 4·d is the family's
convention (Nemotron-4 340B: 18432 → 73728). Rotary over half of each head:
transformers' ``NemotronConfig`` (``partial_rotary_factor`` 0.5), as in
hf:nvidia/Minitron-8B-Base config.json, a model pruned from Nemotron-4 15B.
"""
from repro.configs.base import ModelConfig, register

NEMOTRON_4_15B = register(ModelConfig(
    name="nemotron-4-15b",
    family="dense",
    n_layers=32,
    d_model=6144,
    n_heads=48,
    n_kv_heads=8,
    head_dim=128,
    d_ff=24576,
    vocab_size=256000,
    attn_kind="global",
    mlp_act="sqrelu",
    norm="layernorm",
    rope_theta=10000.0,
    partial_rotary_factor=0.5,
    source="[arXiv:2402.16819 Table 1 + hf:nvidia/Minitron-8B-Base "
           "(partial_rotary_factor); paper + hf]",
))
