"""Config schema: architectures x input shapes (the 40 assigned cells).

``ModelConfig`` is the single source of truth a model is built from; every
assigned architecture is one instance in ``repro/configs/<id>.py``.  A
``ShapeConfig`` names one of the four assigned input shapes.  ``input_specs``
produces ShapeDtypeStruct stand-ins (no allocation) for the dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

MIXER_KINDS = ("attn", "local", "global", "mlstm", "slstm", "rglru", "xattn")


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling (Peng et al. 2023) as the HF ``rope_type: yarn``
    computes it: frequencies blend interpolated (÷ ``factor``) and original
    ones over a wavelength ramp between ``beta_slow`` and ``beta_fast``
    rotations in ``original_max_position`` positions, and cos/sin are scaled
    by ``attention_factor``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention ---
    rope_theta: float = 10_000.0
    rope_theta_local: float = 10_000.0
    rope_yarn: Optional[YaRN] = None      # YaRN on the 'global' layers only
    qk_norm: bool = False
    window: Optional[int] = None          # sliding-window size for 'local'
    layer_pattern: Tuple[str, ...] = ("attn",)
    attn_impl: str = "xla"                # xla | pallas
    # landmark (paper fast-SPSD) attention for long-context decode
    landmark_c: int = 256
    landmark_theta: int = 4
    use_landmark_decode: bool = False     # global layers use LandmarkState cache
    landmark_selection: str = "strided"   # or a SelectionPolicy registry name

    # --- mlp ---
    mlp_variant: str = "swiglu"           # swiglu | geglu | relu2

    # --- moe ---
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25         # training dispatch only
    first_k_dense: int = 0
    dense_d_ff: int = 0
    # training dispatch only: gather | shard_map (serving is dropless,
    # ``moe.moe_ffn_served``, dense or grouped by the call's shape)
    moe_impl: str = "gather"

    # --- MLA (deepseek) ---
    use_mla: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = True               # absorbed (latent-space) decode path

    # --- heads / embeddings ---
    tie_embeddings: bool = False
    scale_embed: bool = False
    norm_eps: float = 1e-6
    post_norm: bool = False               # gemma-style sandwich norm
    mtp: bool = False                     # deepseek multi-token prediction

    # --- encoder-decoder (whisper) ---
    is_encdec: bool = False
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    frontend_dim: int = 0                 # stubbed modality frontend width

    # --- recurrent ---
    rglru_conv_width: int = 4
    lru_width: int = 0
    mlstm_chunk: int = 256

    # --- numerics / compilation ---
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "full"                   # none | full | dots
    scan_layers: bool = True
    unroll_scans: bool = False            # dry-run cost compiles: unroll the
                                          # q-block / mlstm-chunk scans so
                                          # HLO cost analysis counts them
    seq_parallel_attn: bool = False       # sequence-parallel attention for
                                          # heads-misfit archs (H % TP != 0):
                                          # shards q-positions over 'model'
                                          # instead of replicating compute
    chunk_q: int = 1024                   # q-block size of the chunked
                                          # (XLA-flash) attention; smaller
                                          # blocks shrink the f32 score-panel
                                          # transient at slightly worse MXU
                                          # utilization
    fsdp: bool = False                    # also shard embed/ff dims over data
    logits_softcap: Optional[float] = None

    # ----- derived -----
    @property
    def pattern_repeats(self) -> int:
        return self.n_layers // len(self.layer_pattern)

    @property
    def pattern_remainder(self) -> Tuple[str, ...]:
        rem = self.n_layers % len(self.layer_pattern)
        return self.layer_pattern[:rem]

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks), for 6ND roofline."""
        d, v = self.d_model, self.vocab_size
        total = v * d                                    # embed
        if not self.tie_embeddings:
            total += v * d                               # unembed
        for i, kind in enumerate(
                [self.layer_pattern[j % len(self.layer_pattern)]
                 for j in range(self.n_layers)]):
            total += self._mixer_params(kind) + self._mlp_params(i)
            total += 2 * d                               # two norms
        if self.is_encdec:
            # decoder self+cross blocks
            for _ in range(self.n_dec_layers):
                total += 2 * self._mixer_params("attn") + self._mlp_params(0)
                total += 3 * d
        return int(total)

    def _mixer_params(self, kind: str) -> int:
        d, h, kv, hd = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim
        if kind in ("attn", "local", "global", "xattn"):
            if self.use_mla:
                qp = d * self.q_lora_rank + self.q_lora_rank * h * (
                    self.qk_nope_dim + self.qk_rope_dim)
                kvp = d * (self.kv_lora_rank + self.qk_rope_dim)
                kvp += self.kv_lora_rank * h * (self.qk_nope_dim + self.v_head_dim)
                op = h * self.v_head_dim * d
                return qp + kvp + op
            return d * h * hd + 2 * d * kv * hd + h * hd * d
        if kind == "mlstm":
            dq = h * hd
            return d * 2 * dq + 2 * dq * hd * 0 + 3 * d * dq + dq * d  # approx
        if kind == "slstm":
            return 4 * d * h * hd + 4 * h * hd * hd // max(h, 1)
        if kind == "rglru":
            w = self.lru_width or d
            return 2 * d * w + w * self.rglru_conv_width + 2 * w * w + w * d
        return 0

    def _mlp_params(self, layer_idx: int) -> int:
        d = self.d_model
        if self.n_experts and layer_idx >= self.first_k_dense:
            e = self.n_experts * 3 * d * self.moe_d_ff
            e += self.n_shared_experts * 3 * d * self.moe_d_ff
            e += d * self.n_experts                      # router
            return e
        ff = self.dense_d_ff if (self.n_experts and layer_idx < self.first_k_dense) \
            else self.d_ff
        if ff == 0:
            return 0
        mult = 3 if self.mlp_variant in ("swiglu", "geglu") else 2
        return mult * d * ff

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top-k + shared only)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        inactive = (self.n_experts - self.moe_top_k) * 3 * d * self.moe_d_ff
        n_moe_layers = self.n_layers - self.first_k_dense
        return int(total - n_moe_layers * inactive)


# ---------------------------------------------------------------------------
# Input shapes (the four assigned cells per arch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# archs that can run long_500k (sub-quadratic path exists)
LONG_CONTEXT_OK = {"xlstm-125m", "recurrentgemma-2b", "gemma3-12b"}


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train  : {tokens (B, S) i32, labels (B, S) i32}  [+ frontend embeds]
    prefill: {tokens (B, S) i32}
    decode : {tokens (B, 1) i32, pos () i32} + cache specs (built separately)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if cfg.is_encdec:
        # stubbed conv frontend: precomputed frame embeddings, S frames,
        # decoder length S_dec = S // 8 (mechanical; documented in DESIGN.md)
        s_dec = max(S // 8, 1)
        if shape.kind == "train":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), cfg.cdtype),
                "tokens": jax.ShapeDtypeStruct((B, s_dec), i32),
                "labels": jax.ShapeDtypeStruct((B, s_dec), i32),
            }
        if shape.kind == "prefill":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim), cfg.cdtype),
                "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                "pos": jax.ShapeDtypeStruct((), i32)}
    if cfg.family == "vlm" and shape.kind == "train":
        # early fusion: a fixed budget of patch embeddings is prepended
        # (stub frontend); here they are part of the token stream already
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
            "patches": jax.ShapeDtypeStruct((B, 256, cfg.d_model), cfg.cdtype),
        }
    if shape.kind == "train":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32)}
