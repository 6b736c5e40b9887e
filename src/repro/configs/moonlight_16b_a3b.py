"""moonlight-16b-a3b — Moonlight-16B-A3B (moonshotai, DeepSeek-V3 block).

27L d_model=2048 16H, multi-head latent attention (kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v 128, no query latent), layer 0 dense (SwiGLU
11264), layers 1-26 MoE: 64 routed experts of width 1408, top-6 by
sigmoid score + selection bias (noaux_tc, one group), weights normalised
and scaled by 2.446, and 2 shared experts (one SwiGLU of 2816). RoPE base
50,000 on the 64 rope dims, RMSNorm eps 1e-5, untied 163,840-word head.
15.96 B parameters, 2.91 B active per token.
[hf:moonshotai/Moonlight-16B-A3B config.json]
Pure full attention => long_500k cell is skipped.
"""
from repro.models.transformer import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    pattern=(("mla", "moe"),),
    rope_theta=50000.0,
    rms_eps=1e-5,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_dense_lead=1,
    n_experts=64,
    moe_top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    moe_impl="dropless",
    routed_scale=2.446,
    aux_loss_weight=0.0,
)


def reduced() -> ArchConfig:
    """Same structure at CPU size: MLA, one leading dense layer, shared
    experts, sigmoid + bias routing over 8 experts of which this chip
    holds 4."""
    return CONFIG.with_(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
        vocab=512, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_experts=8, moe_top_k=3,
        moe_d_ff=32, n_shared_experts=1, n_held_experts=4,
        attn_chunk=32, loss_chunk=32)
