"""moonlight-16b-a3b — DeepSeek-V3 block: multi-head latent attention
(no low-rank query), one leading dense layer, then routed MoE layers of 64
experts (top-6 by sigmoid scores with a correction bias, weights
renormalised and scaled by 2.446) beside 2 shared experts.
[hf: moonshotai/Moonlight-16B-A3B, config.json]"""
import jax.numpy as jnp

from ..models.lm import LMConfig

FULL = LMConfig(
    name="moonlight-16b-a3b",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163840, rope_theta=50_000.0, norm_eps=1e-5,
    first_k_dense=1, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    block_pattern=("moe",), n_experts=64, top_k=6, d_ff_expert=1408,
    n_shared_experts=2, moe_scoring="sigmoid", moe_routed_scale=2.446,
    param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
    remat="full",
)

REDUCED = LMConfig(
    name="moonlight-16b-a3b-reduced",
    n_layers=3, d_model=128, n_heads=2, n_kv_heads=2,
    d_ff=256, vocab_size=512, norm_eps=1e-5,
    first_k_dense=1, kv_lora_rank=64, qk_nope_dim=32, qk_rope_dim=16,
    v_head_dim=32,
    block_pattern=("moe",), n_experts=16, top_k=4, d_ff_expert=64,
    n_shared_experts=2, moe_scoring="sigmoid", moe_routed_scale=2.446,
)
