"""Pure-jnp oracle for the landmark-attention launch.

Given the context-side factors of the paper's fast model
(k_land (c,d), Ũ (c,c), UV = Ũ(R̂V) (c,dv), U1 = Ũ(R̂1) (c,)), the per-query
read is

    cvec = exp(q @ k_land^T / sqrt(d) - offset)        (m, c)
    out  = (cvec @ UV) / sgnfloor(cvec @ U1, eps)      (m, dv)

where ``sgnfloor`` floors |den| at eps with the sign preserved (an
indefinite fast-U can push the normalizer negative; clamping to +eps would
flip the output sign).  ``landmark_step`` first folds a new key k and value
v in as one more column r = exp(k_land k / sqrt(d) - offset') of R̂, with
offset' = max(offset, max r's logits): UV ← UV e^(offset - offset') +
(Ũ r) vᵀ, U1 ← U1 e^(offset - offset') + Ũ r.
"""
from __future__ import annotations
# repro: allow-file(RPR003: dense f32 oracle — operands are cast to f32 before every contraction)

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def landmark_read(Q: jnp.ndarray, k_land: jnp.ndarray, UV: jnp.ndarray,
                  U1: jnp.ndarray, offset: jnp.ndarray,
                  eps: float = 1e-6) -> jnp.ndarray:
    d = Q.shape[-1]
    inv_sqrt_d = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.matmul(Q.astype(jnp.float32), k_land.astype(jnp.float32).T,
                        precision=HIGHEST) * inv_sqrt_d - offset
    cvec = jnp.exp(logits)
    num = jnp.matmul(cvec, UV.astype(jnp.float32), precision=HIGHEST)
    den = jnp.matmul(cvec, U1.astype(jnp.float32), precision=HIGHEST)
    den = jnp.where(den < 0.0, -1.0, 1.0) * jnp.maximum(jnp.abs(den), eps)
    return (num / den[:, None]).astype(Q.dtype)


def landmark_step(Q, k_land, U, UV, U1, offset, k, v):
    """One factor set: fold key k (d,) and value v (dv,), then read Q (m, d).
    Returns (out (m, dv) f32, (UV, U1, offset))."""
    d = k_land.shape[-1]
    s = jnp.matmul(k_land, k.astype(jnp.float32),
                   precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
    new_off = jnp.maximum(offset, jnp.max(s))
    ur = jnp.matmul(U, jnp.exp(s - new_off), precision=HIGHEST)
    rescale = jnp.exp(offset - new_off)
    UV = UV * rescale + ur[:, None] * v.astype(jnp.float32)[None, :]
    U1 = U1 * rescale + ur
    out = landmark_read(Q.astype(jnp.float32), k_land, UV, U1, new_off)
    return out, (UV, U1, new_off)
