"""kimi-linear-48b-a3b [kda_mla] -- 27L d_model=2304 32H vocab=163840;
20 KDA linear-attention layers beside 7 MLA layers (3 KDA : 1 MLA), MoE
256e top-8 + 1 shared, first layer dense.
[huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct config.json;
Kimi Linear technical report, Moonshot AI, 2025]

Notes:
  * KDA: 32 heads of head dim 128 (dk = dv), short convolution of 4 taps
    over q, k and v; ``kda_layers`` / ``mla_layers`` are the config's
    ``linear_attn_config.kda_layers`` / ``full_attn_layers``, 1-indexed;
  * MLA without a query latent (``q_lora_rank`` null) and without rotary
    embeddings (``mla_use_nope``); the cache keeps kv_lora_rank 512 plus the
    64 ``qk_rope_head_dim`` columns, 576 wide;
  * MoE: sigmoid router, top-8 renormalised, routed output scaled by
    2.446; expert width 1024, dense FFN 9216.
  * decode cache per sequence at S tokens (``models/decode.py``):
    latent (7, S, 576), kda_state (20, 32, 128, 128) float32, kda_conv
    (20, 12288, 3) float32."""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="kimi-linear-48b-a3b",
    family="kda_mla",
    n_layers=27,
    d_model=2304,
    n_heads=32,
    n_kv_heads=32,
    d_ff=9216,
    vocab=163840,
    rope_theta=1e4,
    moe=True,
    n_experts=256,
    n_shared_experts=1,
    top_k=8,
    d_expert=1024,
    first_k_dense=1,
    router_act="sigmoid",
    routed_scale=2.446,
    mla=True,
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    mla_nope=True,
    kda_heads=32,
    kda_head_dim=128,
    kda_conv=4,
    kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                23, 25, 26),
    mla_layers=(4, 8, 12, 16, 20, 24, 27),
    act="silu",
    param_dtype="bfloat16",
)
