"""moonshot-v1-16b-a3b (Moonlight-16B-A3B, ``model_type`` deepseek_v3)
[hf:moonshotai/Moonlight-16B-A3B config.json].

27 layers at d_model=2048: ``first_k_dense_replace`` 1 dense layer
(``intermediate_size`` 11264), then 26 MoE layers of 64 routed experts
of width 1408 (``moe_intermediate_size``), 6 a token, plus 2 shared
experts. Sigmoid scoring with a selection bias (``topk_method``
noaux_tc, ``n_group`` = ``topk_group`` = 1), selected scores normalised
(``norm_topk_prob``) and scaled by 2.446 (``routed_scaling_factor``).
Multi-head latent attention, 16 heads: ``q_lora_rank`` null (a full q
projection), ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128. ``rope_theta`` 50000,
``rms_norm_eps`` 1e-5, untied embeddings, vocab 163840. The Sparton
head is backbone-agnostic (DESIGN.md §4); experts shard over the model
axis (EP).
"""

from repro.configs.base import TransformerConfig, shapes_lm

CONFIG = TransformerConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=11264,
    moe_d_ff=1408,
    vocab_size=163840,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    router="sigmoid",
    routed_scale=2.446,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    # DeepSeek-V3's sequence-wise balance weight (aux_loss_alpha)
    aux_weight=1e-3,
    param_dtype="bfloat16",
    attn_chunk=2048,   # §Perf: -4% memory term vs 512
)

SMOKE = TransformerConfig(
    name="moonshot-smoke",
    family="moe",
    n_layers=2,
    n_dense_layers=1,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_head=16,
    d_ff=128,
    moe_d_ff=32,
    vocab_size=512,
    n_experts=8,
    top_k=2,
    n_shared_experts=2,
    router="sigmoid",
    routed_scale=2.446,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    norm_eps=1e-5,
    tie_embeddings=False,
    aux_weight=1e-3,
    remat=False,
)

SHAPES = shapes_lm(
    long_ok=False,
    long_skip_reason="pure full attention; 524k-token decode needs "
                     "sub-quadratic attention (assignment rule)",
)
