"""Kanana-2-30B-A3B (kakaocorp, model_type deepseek_v3): DeepSeek-V3
blocks [arXiv:2412.19437 §2.1] — MLA without a query LoRA, one leading
dense layer, then 47 layers of 128 fine-grained experts (top 6 by sigmoid
affinity plus a selection bias, weights renormalized and scaled by 2.448,
no dropped tokens) beside 2 shared experts, run here as one SwiGLU of
twice the expert width; no MTP module, no rope scaling, untied head
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601/config.json]."""
from repro.models.config import Block, MLAConfig, MoEConfig, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b", family="moe", d_model=2048,
        vocab_size=128256,
        blocks=(((Block("mla", "dense"),), 1),
                ((Block("mla", "moe"),), 47)),
        num_heads=32, num_kv_heads=32,  # MLA: effectively MHA via latents
        rope_theta=1_000_000.0, d_ff=6144, mlp_act="silu",
        moe=MoEConfig(num_experts=128, top_k=6, d_expert=768,
                      shared_expert=True, d_shared=2 * 768,
                      scoring="sigmoid", routed_scale=2.448, dropless=True),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        norm_eps=1e-6,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="kanana-2-30b-a3b-reduced", family="moe", d_model=256,
        vocab_size=512,
        blocks=(((Block("mla", "dense"),), 1),
                ((Block("mla", "moe"),), 1)),
        num_heads=4, num_kv_heads=4,
        rope_theta=1_000_000.0, d_ff=512, mlp_act="silu",
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                      shared_expert=True, d_shared=128, scoring="sigmoid",
                      routed_scale=2.448, dropless=True),
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32,
                      qk_nope_head_dim=32, qk_rope_head_dim=16,
                      v_head_dim=32),
    )
