"""Public wrappers of the landmark-attention launch: ``landmark_step``
(fold a new key and value into each factor set, then read) and
``landmark_read`` (m queries against one factor set, no fold).  Every
landmark read in the repository goes through them.

Interpret-vs-compile is resolved per call in the un-jitted wrapper (never at
import) and rides the jit cache as a static argument — the
``pairwise.ops._interpret_mode`` idiom.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.landmark_attention import kernel as _k
from repro.kernels.landmark_attention import ref as _ref


def _interpret_mode() -> bool:
    """CPU containers interpret the TPU kernel; real TPU compiles it.

    A function (not a module constant) on purpose: the backend may be chosen
    after this module is imported, so the decision must be re-read per call.
    """
    return jax.default_backend() != "tpu"


def _pad_rows(q: jnp.ndarray) -> jnp.ndarray:
    pad = (-q.shape[1]) % 8
    return jnp.pad(q, ((0, 0), (0, pad), (0, 0))) if pad else q


def landmark_step(q: jnp.ndarray, k_land: jnp.ndarray, U: jnp.ndarray,
                  UV: jnp.ndarray, U1: jnp.ndarray, offset: jnp.ndarray,
                  k_new: jnp.ndarray, v_new: jnp.ndarray,
                  interpret: Optional[bool] = None):
    """Fold one key/value per factor set, then read its G queries.

    q (N, G, d); k_land (N, c, d), U (N, c, c), UV (N, c, dv), U1 (N, c),
    offset (N,) in f32; k_new (N, d), v_new (N, dv).  Returns
    (out (N, G, dv) f32, (UV, U1, offset) updated)."""
    if interpret is None:
        interpret = _interpret_mode()
    N, G, _ = q.shape
    c = U1.shape[-1]
    out, uv, u1, off = _k.landmark_attention_padded(
        _pad_rows(q.astype(jnp.float32)), k_land, UV, U1.reshape(N, 1, c),
        offset.reshape(N, 1, 1), U, k_new[:, None].astype(jnp.float32),
        v_new[:, None].astype(jnp.float32), interpret=interpret)
    return out[:, :G], (uv, u1.reshape(N, c), off.reshape(N))


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _landmark_read_jit(Q, k_land, UV, U1, offset, use_pallas: bool,
                       interpret: bool):
    if not use_pallas:
        return _ref.landmark_read(Q, k_land, UV, U1, offset)
    c = U1.shape[-1]
    out = _k.landmark_attention_padded(
        _pad_rows(Q.astype(jnp.float32)[None]),
        k_land.astype(jnp.float32)[None], UV.astype(jnp.float32)[None],
        U1.astype(jnp.float32).reshape(1, 1, c),
        jnp.asarray(offset, jnp.float32).reshape(1, 1, 1),
        interpret=interpret)
    return out[0, :Q.shape[0]].astype(Q.dtype)


def landmark_read(Q: jnp.ndarray, k_land: jnp.ndarray, UV: jnp.ndarray,
                  U1: jnp.ndarray, offset: jnp.ndarray,
                  use_pallas: bool = True,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Attend Q (m, d) to one prebuilt factor set -> (m, dv)."""
    if interpret is None:
        interpret = _interpret_mode()
    return _landmark_read_jit(Q, k_land, UV, U1, offset, use_pallas,
                              interpret)
