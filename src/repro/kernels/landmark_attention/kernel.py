"""Fused landmark-attention read (the paper's fast model on the softmax Gram).

After ``build_landmark_state`` has produced the context-side factors
(k_land, UV = U^fast (R̂ V), U1 = U^fast (R̂ 1)), attending m queries to an
n-token context costs O(m * c * d) — *independent of n*.  This kernel fuses

    exp(Q K_land^T / sqrt(d) - offset)  ->  (. @ UV) / (. @ U1)

so the (m, c) score panel never leaves VMEM:

- Q is tiled (BQ, d); k_land (c, d), UV (c, dv), U1 (c, 1) are VMEM-resident
  per tile (c <= a few hundred landmarks, ~KBs);
- both GEMMs hit the MXU; exp and the divide run on the VPU;
- HBM traffic per tile: BQ*d in, BQ*dv out — the roofline-optimal minimum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 128


def _landmark_kernel(q_ref, kl_ref, uv_ref, u1_ref, off_ref, o_ref, *,
                     eps: float):
    q = q_ref[...].astype(jnp.float32)                      # (bq, d)
    kl = kl_ref[...].astype(jnp.float32)                    # (c, d)
    uv = uv_ref[...].astype(jnp.float32)                    # (c, dv)
    u1 = u1_ref[...].astype(jnp.float32)                    # (c, 1)
    off = off_ref[0, 0]

    d = q.shape[1]
    inv_sqrt_d = 1.0 / (d ** 0.5)
    logits = jax.lax.dot_general(
        q, kl, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * inv_sqrt_d - off
    cvec = jnp.exp(logits)                                  # (bq, c)
    num = jax.lax.dot(cvec, uv, preferred_element_type=jnp.float32)
    den = jax.lax.dot(cvec, u1, preferred_element_type=jnp.float32)
    # sign-preserving floor: an indefinite fast-U can push den negative, and
    # a plain maximum(den, eps) would flip the sign of the whole output row
    den = jnp.where(den < 0.0, -1.0, 1.0) * jnp.maximum(jnp.abs(den), eps)
    o_ref[...] = (num / den).astype(o_ref.dtype)


def landmark_read_padded(Q: jnp.ndarray, k_land: jnp.ndarray,
                         UV: jnp.ndarray, U1: jnp.ndarray,
                         offset: jnp.ndarray, eps: float = 1e-6,
                         interpret: bool = False) -> jnp.ndarray:
    m, d = Q.shape
    c, dv = UV.shape
    assert m % BLOCK_Q == 0, m
    grid = (m // BLOCK_Q,)
    off2 = jnp.asarray(offset, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_landmark_kernel, eps=eps),
        name="landmark_attention",
        metadata={"kernel": "landmark_attention"},
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, d), lambda i: (i, 0)),
            pl.BlockSpec((c, d), lambda i: (0, 0)),
            pl.BlockSpec((c, dv), lambda i: (0, 0)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, dv), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, dv), Q.dtype),
        interpret=interpret,
    )(Q, k_land, UV, U1.reshape(c, 1), off2)
