"""phi3.5-moe-42b-a6.6b [moe]: 16 experts, sparsemixer top-2.

32L d_model=4096 32H (GQA kv=8, head 128) d_ff=6400 vocab=32064
[hf:microsoft/Phi-3.5-MoE-instruct, `PhiMoEForCausalLM`]: pre-norm
LayerNorm with weight and bias (eps 1e-5); biases on q, k, v and o; a
4096 -> 16 router with no bias and sparsemixer top-2 routing
(``router_jitter_noise`` 0.01); SwiGLU experts; an untied head with a
bias. RoPE is plain at theta 1e4: the published ``longrope`` factors
are not in this repository, and at contexts under 4096 only their short
factors would apply.
"""
from repro.configs import ArchConfig

CONFIG = ArchConfig(
    name="phi3.5-moe-42b-a6.6b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=6400,
    vocab_size=32064,
    qkv_bias=True,
    rope_theta=1e4,
    norm_eps=1e-5,
    n_experts=16,
    experts_per_token=2,
    router="sparsemixer",
    router_jitter=0.01,
    norm="layer",
    o_bias=True,
    head_bias=True,
    supports_long_context=False,
)

SMOKE = ArchConfig(
    name="phi3.5-moe-smoke",
    family="moe",
    n_layers=3,
    d_model=128,
    n_heads=8,
    n_kv_heads=2,
    d_ff=192,
    vocab_size=512,
    qkv_bias=True,
    n_experts=4,
    experts_per_token=2,
    router="sparsemixer",
    router_jitter=0.01,
    norm="layer",
    o_bias=True,
    head_bias=True,
)
