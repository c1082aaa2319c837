"""mellum2-12b-a2.5b — routed experts, window and full attention mixed,
untied head [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

One chip's share of a four-chip deployment: each layer's 64 experts are
divided four ways (expert parallelism 4) and this chip holds experts
0-15; the router keeps its 64 outputs and top-8. Attention, embedding and
head are held whole. Every width is as published: 28 layers of GQA
(32 query heads, 4 KV heads of 128), experts of width 896, every fourth
layer full attention with YaRN RoPE, the others a 1,024-token window with
plain RoPE, both at theta 500,000. Query/key RMSNorm per head is assumed,
as in Qwen3-MoE, whose keys the published config carries.
"""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=896,  # per-expert FFN width
    vocab_size=98_304,
    qk_norm=True,
    sliding_window=1024,
    full_attn_every=4,
    rope_theta=500_000.0,
    yarn=Yarn(factor=16.0, original_max_position=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    num_experts=16,  # held here: experts 0-15 of 64
    routed_experts=64,
    top_k=8,
    tie_embeddings=False,
)
