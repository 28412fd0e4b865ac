"""Shared layers: norms, embeddings, RoPE, MLP variants.

Pure-function style: ``init_*`` returns a params pytree; ``apply`` functions
take (params, x).  Sharding is attached *by name* via the rules in
``repro.distributed.sharding`` — parameter path names here are load-bearing.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}          # gemma-style (1+scale)


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embed(key: jax.Array, cfg: ModelConfig) -> dict:
    # tied embeddings double as the unembed: std d^-1/2 keeps init-time
    # logits O(1) (scale_embed restores O(1) input activations).
    emb_std = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    p = {"embedding": dense_init(key, (cfg.vocab_size, cfg.d_model),
                                 cfg.pdtype, scale=emb_std)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(jax.random.fold_in(key, 1),
                                  (cfg.d_model, cfg.vocab_size), cfg.pdtype)
    return p


def embed(params: dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(params["embedding"].astype(cfg.cdtype), tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.cdtype)
    return x


def unembed(params: dict, cfg: ModelConfig, x: jnp.ndarray,
            out_dtype=None) -> jnp.ndarray:
    """Logits in the compute dtype, or accumulated into ``out_dtype``."""
    if cfg.tie_embeddings:
        w = params["embedding"].astype(cfg.cdtype).T
    else:
        w = params["unembed"].astype(cfg.cdtype)
    logits = jnp.matmul(x, w, preferred_element_type=out_dtype)
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float, yarn=None) -> jnp.ndarray:
    """Inverse frequencies θ^(−2i/D); with ``yarn`` (``configs.base.YaRN``)
    the HF ``rope_type: yarn`` blend: dimensions that turn fewer than
    ``beta_slow`` times in ``original_max_position`` positions are
    interpolated (÷ factor), those that turn more than ``beta_fast`` times
    keep their frequency, and a linear ramp joins the two."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    if yarn is None:
        return inv

    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: original, 0: interpolated
    return inv / yarn.factor * (1.0 - keep) + inv * keep


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn=None) -> jnp.ndarray:
    """x: (..., S, D) with positions (S,) or (..., S); rotate-half pairs
    (i, i + D/2).  ``yarn`` scales the frequencies and cos/sin."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta, yarn)                 # (D/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos = cos * yarn.attention_factor
        sin = sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_for(cfg: ModelConfig, kind: str):
    """(θ, YaRN or None) of a mixer kind: local layers take
    ``rope_theta_local``; YaRN applies to ``global`` layers only."""
    if kind == "local":
        return cfg.rope_theta_local, None
    return cfg.rope_theta, cfg.rope_yarn if kind == "global" else None


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key: jax.Array, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    ff = cfg.d_ff if d_ff is None else d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        return {"wi_gate": dense_init(k1, (d, ff), cfg.pdtype),
                "wi_up": dense_init(k2, (d, ff), cfg.pdtype),
                "wo": dense_init(k3, (ff, d), cfg.pdtype)}
    return {"wi_up": dense_init(k2, (d, ff), cfg.pdtype),
            "wo": dense_init(k3, (ff, d), cfg.pdtype)}


def mlp(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    dt = cfg.cdtype
    up = x @ params["wi_up"].astype(dt)
    if cfg.mlp_variant == "swiglu":
        gate = jax.nn.silu(x @ params["wi_gate"].astype(dt))
        h = gate * up
    elif cfg.mlp_variant == "geglu":
        gate = jax.nn.gelu(x @ params["wi_gate"].astype(dt), approximate=True)
        h = gate * up
    elif cfg.mlp_variant == "relu2":
        h = jnp.square(jax.nn.relu(up))
    elif cfg.mlp_variant == "gelu":
        h = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(cfg.mlp_variant)
    return h @ params["wo"].astype(dt)
