"""Landmark attention (the paper's fast model on the softmax Gram): one
launch folds each new key and value into the factors and reads them.

The context-side factors of one (session, KV head) are the landmark keys
k_land (c, d), the fast-CUR Ũ (c, c), UV = Ũ (R̂ V) (c, dv) and U1 = Ũ (R̂ 1),
stored as a (1, c) row, under a shared offset ``off`` inside every exp.
Decoding key k_t and value v_t folds them in as one more column of R̂:

    r   = exp(k_land k_t / √d − off')          off' = max(off, max_c of that)
    UV ← UV · e^(off − off') + (Ũ r) v_tᵀ       U1 ← U1 · e^(off − off') + (Ũ r)ᵀ

and the G queries of the group read the updated factors:

    cvec = exp(q k_landᵀ / √d − off')    out = (cvec UV) / sgnfloor(cvec · U1)

Raising the offset to the new key's largest logit keeps exp from
overflowing on keys larger than the prompt's; scaling UV and U1 by the same
factor leaves every read unchanged (numerator and denominator scale
together).  Without a new key (``fold=False``) the launch only reads.

The grid runs over the N = sessions × KV heads factor sets, one per step;
the step's whole state is VMEM-resident (≈1.5 MiB at c = 512, d = 128) and
UV, U1 and the offset are updated in place (``input_output_aliases``).  All
contractions are f32 at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))           # contract the last dims: A Bᵀ


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _landmark_kernel(*refs, fold: bool, eps: float, scale: float):
    if fold:
        (q_ref, kl_ref, u_ref, uv_ref, u1_ref, off_ref, kn_ref, vn_ref,
         o_ref, uv_out, u1_out, off_out) = refs
    else:
        q_ref, kl_ref, uv_ref, u1_ref, off_ref, o_ref = refs
    kl = kl_ref[0]                                           # (c, d)
    uv = uv_ref[0]                                           # (c, dv)
    u1 = u1_ref[0]                                           # (1, c)
    off = off_ref[0]                                         # (1, 1)
    if fold:
        kn = kn_ref[0].astype(jnp.float32)                   # (1, d)
        vn = vn_ref[0].astype(jnp.float32)                   # (1, dv)
        s = _dot_nt(kn, kl) * scale                          # (1, c)
        new_off = jnp.maximum(off, jnp.max(s, axis=1, keepdims=True))
        rescale = jnp.exp(off - new_off)                     # (1, 1)
        r = jnp.exp(s - new_off)                             # (1, c)
        u = u_ref[0]                                         # (c, c)
        ur_col = _dot_nt(u, r)                               # Ũ r  (c, 1)
        ur_row = _dot_nt(r, u)                               # r Ũᵀ (1, c)
        uv = uv * rescale + ur_col * vn
        u1 = u1 * rescale + ur_row
        off = new_off
        uv_out[0] = uv
        u1_out[0] = u1
        off_out[0] = off
    q = q_ref[0].astype(jnp.float32)                         # (G, d)
    cvec = jnp.exp(_dot_nt(q, kl) * scale - off)             # (G, c)
    num = jax.lax.dot(cvec, uv, precision=HIGHEST,
                      preferred_element_type=jnp.float32)    # (G, dv)
    den = jnp.sum(cvec * u1, axis=1, keepdims=True)          # (G, 1)
    # sign-preserving floor: an indefinite fast-U can push den negative, and
    # a plain maximum(den, eps) would flip the sign of the whole output row
    den = jnp.where(den < 0.0, -1.0, 1.0) * jnp.maximum(jnp.abs(den), eps)
    o_ref[0] = (num / den).astype(o_ref.dtype)


def launch_work(n: int, g: int, c: int, d: int, dv: int,
                fold: bool) -> Dict[str, int]:
    """FLOPs and bytes one launch issues at its launch shapes.

    FLOPs per factor set: the read (q k_landᵀ, cvec UV, cvec · U1: 2gcd +
    2gc·dv + 2gc) and, with a fold, k_t k_landᵀ, Ũ r as a column and as a
    row and the rescaled rank-one update (2cd + 4c² + 3c·dv + 2c).

    ``bytes`` counts, in f32, what the launch streams: the queries, k_land
    and the result, and with a fold Ũ and the new key and value; without a
    fold also UV, U1 and the offset, which it only reads.
    ``inplace_bytes`` is the factor state a folding launch updates in place
    (UV, U1, the offset: ``input_output_aliases``).  A decode loop keeps that
    state resident in on-chip memory from step to step (the compiled turn
    assigns the aliased results memory space 1), so its traffic is not the
    launch's; a caller that holds it in HBM adds it twice."""
    flops = 2 * g * c * d + 2 * g * c * dv + 2 * g * c
    nbytes = 4 * (g * d + c * d + g * dv)
    state = 4 * (c * dv + c + 1)
    if fold:
        flops += 2 * c * d + 4 * c * c + 3 * c * dv + 2 * c
        nbytes += 4 * (c * c + d + dv)
        return {"flops": n * flops, "bytes": n * nbytes,
                "inplace_bytes": n * state}
    return {"flops": n * flops, "bytes": n * (nbytes + state),
            "inplace_bytes": 0}


def launch_record(n: int, g: int, c: int, d: int, dv: int,
                  fold: bool) -> Dict[str, str]:
    """The launch's ``metadata``, in the format of the pairwise launches'
    records (``kernels/pairwise/kernel.launch_record``): its kernel name
    and the work it issues (``launch_work``), as strings."""
    work = launch_work(n, g, c, d, dv, fold)
    return {"kernel": "landmark_attention", "flops": str(work["flops"]),
            "bytes": str(work["bytes"]),
            "inplace_bytes": str(work["inplace_bytes"]),
            "fold": str(int(fold))}


def landmark_attention_padded(q, k_land, UV, U1, off, U=None, k_new=None,
                              v_new=None, eps: float = 1e-6,
                              interpret: bool = False):
    """q (N, G, d) with G a multiple of 8; k_land (N, c, d), UV (N, c, dv),
    U1 (N, 1, c), off (N, 1, 1) in f32; with a fold also U (N, c, c),
    k_new (N, 1, d) and v_new (N, 1, dv).  Returns out (N, G, dv) f32 and,
    with a fold, the updated (UV, U1, off), written over their inputs."""
    N, G, d = q.shape
    c, dv = UV.shape[1], UV.shape[2]
    fold = U is not None
    assert G % 8 == 0, G

    def blk(*shape):
        return pl.BlockSpec((1,) + shape, lambda i: (i,) + (0,) * len(shape))

    if fold:
        operands = (q, k_land, U, UV, U1, off, k_new, v_new)
        in_specs = [blk(G, d), blk(c, d), blk(c, c), blk(c, dv), blk(1, c),
                    blk(1, 1), blk(1, d), blk(1, dv)]
        out_specs = (blk(G, dv), blk(c, dv), blk(1, c), blk(1, 1))
        out_shape = (jax.ShapeDtypeStruct((N, G, dv), jnp.float32),
                     jax.ShapeDtypeStruct(UV.shape, jnp.float32),
                     jax.ShapeDtypeStruct(U1.shape, jnp.float32),
                     jax.ShapeDtypeStruct(off.shape, jnp.float32))
        aliases = {3: 1, 4: 2, 5: 3}
    else:
        operands = (q, k_land, UV, U1, off)
        in_specs = [blk(G, d), blk(c, d), blk(c, dv), blk(1, c), blk(1, 1)]
        out_specs = blk(G, dv)
        out_shape = jax.ShapeDtypeStruct((N, G, dv), jnp.float32)
        aliases = {}
    return pl.pallas_call(
        functools.partial(_landmark_kernel, fold=fold, eps=eps,
                          scale=1.0 / (d ** 0.5)),
        name="landmark_attention",
        metadata=launch_record(N, G, c, d, dv, fold),
        grid=(N,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
