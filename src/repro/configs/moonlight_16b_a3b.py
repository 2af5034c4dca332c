"""moonlight-16b-a3b — DeepSeek-V3-style MoE: latent attention, one leading
dense layer, 64 sigmoid-routed experts top-6 plus 2 shared
[hf:moonshotai/Moonlight-16B-A3B config.json; model_type deepseek_v3].

From the published config: 27 layers, hidden 2,048, 16 heads, MLA with
``q_lora_rank`` null, ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128; ``first_k_dense_replace`` 1
(layer 0 a SwiGLU of width 11,264); then 64 routed experts of width
1,408 and 2 shared, ``scoring_func`` sigmoid, ``topk_method`` noaux_tc
(a per-expert correction bias used only to select), ``n_group`` =
``topk_group`` = 1, ``norm_topk_prob`` true, ``routed_scaling_factor``
2.446; vocabulary 163,840, untied; ``rope_theta`` 50,000;
``rms_norm_eps`` 1e-5.
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, ATTN

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11_264,
    vocab_size=163_840,
    rope_theta=50_000.0,
    block_pattern=(ATTN,),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    first_dense_layers=1,
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                  num_shared_experts=2, scoring="sigmoid",
                  selection_bias=True, routed_scale=2.446),
    tie_embeddings=False,
    rms_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B",
)
