"""moonlight-16b-a3b [moe] — DeepSeek-V3 block: multi-head latent attention,
64 sigmoid-routed experts (top-6, normalised, scaled 2.446) beside 2 shared
experts, one leading dense layer [hf:moonshotai/Moonlight-16B-A3B
config.json; arXiv:2412.19437, arXiv:2405.04434].

``experts_held`` 64: the whole layer. The benchmark's configuration holds
one chip's share of an expert-parallel deployment (bench/configs).
"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                  # moe_intermediate_size
    vocab_size=163840,
    head_dim=192,               # qk_nope_head_dim + qk_rope_head_dim
    n_experts=64,
    experts_per_token=6,
    shared_expert=True,
    shared_d_ff=2816,           # n_shared_experts 2 x 1408
    routed_scale=2.446,
    experts_held=64,
    first_k_dense=1,
    dense_d_ff=11264,           # intermediate_size
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B",
))
