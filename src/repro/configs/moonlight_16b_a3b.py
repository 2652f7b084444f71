"""Moonlight-16B-A3B (DeepSeek-V3 block). [hf:moonshotai/Moonlight-16B-A3B]

27L d_model=2048 vocab=163840, untied. Attention: 16 heads of MLA without
a query LoRA (kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128), plain
RoPE at theta 50000. Layer 0: a dense SiLU MLP of width 11264. Layers
1-26: 64 routed experts of width 1408, 6 per token, chosen by sigmoid score
plus a correction bias (noaux_tc, one group), weighted by the chosen
sigmoid scores normalised over the 6 and scaled by 2.446; two shared
experts as one MLP of width 2816.

``ep8_share()`` is what one chip holds of the stated deployment (experts
over 8 chips, attention data-parallel): 8 of the 64 routed experts of
every MoE layer, over layer 0 and MoE layers 1-15 (the rest would lie on a
further pipeline stage).
"""
from repro.config import MLAConfig, ModelConfig, MoEConfig


def _moe(num_experts: int, top_k: int, d_expert: int, d_shared: int,
         held: int = 0) -> MoEConfig:
    return MoEConfig(num_experts=num_experts, top_k=top_k,
                     router="sigmoid_bias", d_expert=d_expert,
                     d_shared=d_shared, route_scale=2.446, experts_held=held)


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,                 # qk_nope 128 + qk_rope 64
        d_ff=11264,                   # the dense layer's width
        vocab_size=163_840,
        pattern=("attn",),
        moe=_moe(64, 6, 1408, 2 * 1408),
        first_dense=1,
        mla=MLAConfig(kv_lora_rank=512, nope_dim=128, rope_dim=64,
                      v_dim=128),
        rope_theta=50_000.0,
    )


def ep8_share() -> ModelConfig:
    """One chip's share under expert parallelism over 8 chips: experts
    0-7 of each MoE layer, layer 0 and MoE layers 1-15."""
    c = config()
    return c.with_(name="moonlight-16b-a3b-ep8", num_layers=16,
                   moe=_moe(64, 6, 1408, 2 * 1408, held=8))


def reduced() -> ModelConfig:
    return ModelConfig(
        name="moonlight-reduced",
        family="moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=48,
        d_ff=128,
        vocab_size=256,
        pattern=("attn",),
        moe=_moe(8, 3, 32, 64, held=4),
        first_dense=1,
        mla=MLAConfig(kv_lora_rank=32, nope_dim=32, rope_dim=16, v_dim=16),
    )
