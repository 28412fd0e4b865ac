"""mellum2-12b-a2.5b [moe] — 3:1 sliding:full interleave, 64 experts top-8
(hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json).

28L d_model=2304 32H (kv=4) head_dim=128 vocab=98304, untied head.  Every
layer is a sparse MoE: 64 experts of width 896, top-8 softmax routing with
``norm_topk_prob``, SwiGLU, no shared expert.  Sliding layers (window 1024)
use the default RoPE at θ = 500000; full layers use YaRN (factor 16 over
8192 original positions, β_fast 32, β_slow 1, attention factor 1.27726).
RMSNorm ε 1e-6, no attention bias, no q/k norm (the config names none).

Serving: the full (``global``) layers decode through the paper's fast model
on the softmax Gram (landmark attention, c = 512, θ = 4, strided landmarks)
and fold every decoded token into its factors.  Weights and activations are
bf16; logits, softmax, the router and the landmark factors are f32.  The
checkpoint's multi-token-prediction head is not modelled (serving decodes
one token per step).
"""
from repro.configs.base import ModelConfig, YaRN

PATTERN = ("local", "local", "local", "global")

FULL = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim=128,
    d_ff=0, vocab_size=98_304,
    layer_pattern=PATTERN, window=1024,
    rope_theta=500_000.0, rope_theta_local=500_000.0,
    rope_yarn=YaRN(factor=16.0, original_max_position=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782),
    n_experts=64, moe_top_k=8, moe_d_ff=896,
    norm_eps=1e-6,
    use_landmark_decode=True, landmark_c=512, landmark_theta=4,
    landmark_selection="strided",
    dtype="bfloat16", param_dtype="bfloat16",
    # a 256-row q block keeps prefill's f32 score panel at 1 GiB per
    # (32 heads × 32768 keys) panel
    chunk_q=256,
)

SMOKE = ModelConfig(
    name="mellum2-12b-a2.5b-smoke", family="moe",
    n_layers=4, d_model=64, n_heads=8, n_kv_heads=2, head_dim=16,
    d_ff=0, vocab_size=256,
    layer_pattern=PATTERN, window=8,
    rope_theta=500_000.0, rope_theta_local=500_000.0,
    rope_yarn=YaRN(factor=16.0, original_max_position=32, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782),
    n_experts=16, moe_top_k=4, moe_d_ff=32,
    norm_eps=1e-6,
    use_landmark_decode=True, landmark_c=8, landmark_theta=2,
    landmark_selection="strided",
    dtype="float32", param_dtype="float32",
)
