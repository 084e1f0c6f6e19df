"""kimi-k2-1t-a32b [mla_moe] — Kimi-K2-Instruct: DeepSeek-V3's block, MLA
over 384 experts (top-8 by sigmoid score, one shared), one leading dense
layer, YaRN rope.
[hf:moonshotai/Kimi-K2-Instruct config.json]"""
from repro.configs.base import MlaMoeConfig, register

CONFIG = register(
    MlaMoeConfig(
        name="kimi-k2-1t-a32b",
        family="mla_moe",
        n_layers=61,
        d_model=7168,
        n_heads=64,
        n_kv_heads=64,
        head_dim=192,  # q/k: 128 no-rope + 64 rope dims
        d_ff=18432,  # the leading dense layer
        vocab=163840,
        rope_theta=50000.0,
        norm_eps=1e-6,
        n_experts=384,
        top_k=8,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        moe_d_ff=2048,
        n_shared_experts=1,
        route_scale=2.827,
        yarn_factor=32.0,
        yarn_orig_max_pos=4096,
        yarn_beta_fast=1.0,
        yarn_beta_slow=1.0,
        yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0,
        param_dtype="bfloat16",  # 1T params: fp32 master impossible at 512 chips
        zero1=True,
        remat="full",
    )
)
