"""qwen3-moe-235b-a22b — 94L d_model=4096 64H (GQA kv=4), MoE 128e top-8.

[hf:Qwen/Qwen3-235B-A22B config.json (model_type qwen3_moe); Qwen3 Technical
Report, arXiv:2505.09388]  Every layer is MoE (decoder_sparse_step 1, no
mlp_only_layers): 128 routed SwiGLU experts of width 1536, top-8 with the
gates renormalised over the 8 picks (norm_topk_prob), no shared experts.
Attention: GQA 64/4 heads of 128 with a per-head RMSNorm of q and k before
RoPE (theta 1e6); rms_norm_eps 1e-6; vocab 151936, untied.
"""
from repro.configs.base import AttnConfig, BlockConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="qwen3-moe-235b-a22b",
    d_model=4_096,
    vocab=151_936,
    norm_eps=1e-6,
    blocks=(
        BlockConfig(
            kind="moe",
            n_layers=94,
            attn=AttnConfig(kind="gqa", n_heads=64, n_kv_heads=4, d_head=128,
                            rope_theta=1_000_000.0, qk_norm=True),
            moe=MoEConfig(
                n_experts=128, top_k=8, d_ff=1_536, n_shared=0,
                capacity_factor=1.25, aux_free_bias=False,
            ),
        ),
    ),
    remat="full",
)

SMOKE = ModelConfig(
    name="qwen3-moe-smoke",
    d_model=64,
    vocab=256,
    blocks=(
        BlockConfig(
            kind="moe",
            n_layers=2,
            attn=AttnConfig(kind="gqa", n_heads=4, n_kv_heads=2, d_head=16,
                            qk_norm=True),
            moe=MoEConfig(n_experts=8, top_k=2, d_ff=32, aux_free_bias=False),
        ),
    ),
)
