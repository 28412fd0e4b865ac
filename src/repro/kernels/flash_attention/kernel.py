"""Tiled online-softmax (flash) attention Pallas kernel for TPU.

Design points (TPU-adapted, not a CUDA port):

- grid = (B, Hq, nq, nk); the trailing ``nk`` axis is sequential on TPU, so the
  per-(B, H, q-tile) running state (m, l, acc) lives in VMEM scratch and is
  carried across the k-tiles — no atomics, no shared-memory reduction tree.
- GQA is an *index-map* trick: the K/V BlockSpecs map q-head h to kv-head
  ``h // group`` so grouped heads reread the same KV tile from HBM (which the
  compiler keeps in VMEM across adjacent grid steps) instead of materializing
  ``jnp.repeat``'d KV.
- blocks are (BQ, D) x (BK, D) with BQ = BK = 128: the s-tile (128 x 128) and
  p @ v both hit the MXU with f32 accumulation; masks are VPU iota compares.
- causal + sliding-window masking is positional, supporting the decode case
  (Sq < Sk) by right-aligning queries to keys.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, sq: int, sk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                     # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                     # (bk, dv)

    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale         # (bq, bk)

    row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    offs = sk - sq                                          # decode alignment
    mask = col < sk                                         # K padding
    if causal:
        mask &= col <= (row + offs)
    if window is not None:
        mask &= ((row + offs) - col) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                     # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)     # all-masked tiles
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)

    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = alpha * acc_scr[...] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_padded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           sq: int, sk: int, causal: bool,
                           window: Optional[int],
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jnp.ndarray:
    """Padded call: q (B,Hq,SQp,D), k/v (B,Hkv,SKp,D); SQp/SKp tile multiples.

    ``sq``/``sk`` are the unpadded logical lengths used for masking.
    """
    B, Hq, SQp, D = q.shape
    Hkv, SKp = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    assert SQp % bq == 0 and SKp % bk == 0, (SQp, SKp, bq, bk)
    group = Hq // Hkv
    grid = (B, Hq, SQp // bq, SKp // bk)

    scale = 1.0 / (float(D) ** 0.5)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=sq, sk=sk)

    return pl.pallas_call(
        kern,
        name="flash_attention",
        metadata={"kernel": "flash_attention"},
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, SQp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
