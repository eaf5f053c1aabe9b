"""moonshot-v1-16b-a3b (Moonlight-16B-A3B) — latent attention and a
fine-grained MoE with shared experts (DeepSeek-V3 block).

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``model_type`` deepseek_v3). 27 layers at hidden 2048, untied vocabulary
of 163,840. Attention: MLA over 16 heads, ``q_lora_rank`` null,
``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64,
``v_head_dim`` 128, ``rope_theta`` 50,000, no rope scaling. Layer 0 is
dense (``first_k_dense_replace`` 1, MLP 11,264); layers 1-26 route each
token to 6 of 64 experts of width 1,408 (``sigmoid`` scores plus a
choice-only correction bias, ``noaux_tc`` with one group,
``norm_topk_prob``, ``routed_scaling_factor`` 2.446) beside 2 shared
experts. The auxiliary-loss-free bias update and the sequence aux loss
have no published rate and are left out (``router_aux_weight`` 0).
"""
from repro.configs.base import ArchConfig, BLOCK_ATTN

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    experts_per_token=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_k_dense=1,
    router_score="sigmoid",
    router_scale=2.446,
    router_aux_weight=0.0,
    block_type=BLOCK_ATTN,
    rope_theta=5e4,
    norm_eps=1e-5,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)
