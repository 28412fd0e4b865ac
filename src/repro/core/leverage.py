"""Leverage scores and coherence (paper §2)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _default_rcond(shape) -> float:
    """numpy-style cutoff: max(m, n) * eps(f32).  1e-10 keeps numerically-zero
    singular values in f32 and destroys the pinv — see tests/test_spsd_properties."""
    return max(shape) * float(jnp.finfo(jnp.float32).eps)


def row_leverage_scores(A: jnp.ndarray, rcond: float = None) -> jnp.ndarray:
    """l_i = ||u_i:||^2 where A = U Σ V^T is the condensed SVD.

    Computed from the thin SVD in f32.  Sum of scores equals rank(A).
    """
    rcond = _default_rcond(A.shape) if rcond is None else rcond
    A32 = A.astype(jnp.float32)
    u, s, _ = jnp.linalg.svd(A32, full_matrices=False)
    cutoff = rcond * jnp.max(s)
    mask = (s > cutoff).astype(jnp.float32)
    return jnp.sum((u * mask[None, :]) ** 2, axis=1)


def column_leverage_scores(A: jnp.ndarray, rcond: float = None) -> jnp.ndarray:
    return row_leverage_scores(A.T, rcond)


def _gram_leverage(panel_fn, nrows: int, dim: int, block_size, mesh):
    """l_i = p_i (Σ panelsᵀ panels)† p_iᵀ over (b × dim) panels: a blocked
    Gram pass then a blocked quadratic-form pass through the sweep engine
    (``repro.core.sweep``) — peak memory O(b·dim + dim²), shardable."""
    from repro.core.sweep import GramPlan, RowQuadFormPlan, sweep_panels
    (G,) = sweep_panels(panel_fn, nrows, dim, [GramPlan(dim)],
                        block_size=block_size, mesh=mesh)
    W = pinv(0.5 * (G + G.T))
    (lev,) = sweep_panels(panel_fn, nrows, dim, [RowQuadFormPlan(W)],
                          block_size=block_size, mesh=mesh)
    return lev


def row_leverage_scores_gram(A: jnp.ndarray, block_size: int = None,
                             mesh=None) -> jnp.ndarray:
    """Row leverage scores of a tall A (m × c) via a blocked Gram AᵀA pass.

    l_i = a_i (AᵀA)† a_iᵀ — identical to the SVD route (for σ > 0 masked
    consistently) but no m×c transposed copy or O(m·c²) SVD workspace is
    ever staged.
    """
    m, cdim = A.shape
    return _gram_leverage(lambda idx: jnp.take(A, idx, axis=0), m, cdim,
                          block_size, mesh)


def column_leverage_scores_gram(R: jnp.ndarray, block_size: int = None,
                                mesh=None) -> jnp.ndarray:
    """Column (row-space) leverage scores of a wide R (r × n), streamed.

    The CUR R-side scores: l_j = R_:jᵀ (R Rᵀ)† R_:j.  PR 1 densified the
    n × r transpose and ran an SVD — fine at paper scale, not at n ≫ 10⁵;
    here the Gram R Rᵀ accumulates over (b × r) column panels instead.
    """
    r, n = R.shape
    return _gram_leverage(lambda idx: jnp.take(R, idx, axis=1).T, n, r,
                          block_size, mesh)


def row_coherence(A: jnp.ndarray) -> jnp.ndarray:
    """mu(A) = (m / rank) * max_i l_i  in [1, m]."""
    lev = row_leverage_scores(A)
    rank = jnp.sum(lev)
    return A.shape[0] / rank * jnp.max(lev)


def pinv(A: jnp.ndarray, rcond: float = None) -> jnp.ndarray:
    """Moore-Penrose inverse via f32 SVD (small s×c / c×c blocks only)."""
    rcond = _default_rcond(A.shape) if rcond is None else rcond
    A32 = A.astype(jnp.float32)
    u, s, vt = jnp.linalg.svd(A32, full_matrices=False)
    cutoff = rcond * jnp.max(s)
    sinv = jnp.where(s > cutoff, 1.0 / s, 0.0)
    return jnp.matmul(vt.T * sinv[None, :], u.T,
                      precision=jax.lax.Precision.HIGHEST)


def orthonormal_basis(A: jnp.ndarray, rcond: float = None) -> jnp.ndarray:
    """Orthonormal basis of range(A) (Algorithm 1, step 3 'optional')."""
    A32 = A.astype(jnp.float32)
    u, s, _ = jnp.linalg.svd(A32, full_matrices=False)
    return u  # zero-singular-value columns contribute nothing downstream
