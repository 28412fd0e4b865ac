"""CUR matrix decomposition (paper §5).

Given A (m×n), C = c columns, R = r rows:

- optimal:    U* = C† A R†                              (Eq. 8)  O(mn·min(c,r))
- drineas08:  U  = (P_R^T A P_C)†                        (Fig. 2c baseline)
- fast:       Ũ  = (S_C^T C)† (S_C^T A S_R) (R S_R)†     (Eq. 9)  O(cr/ε · min(m,n) · min(c,r))

plus the adaptive-sampling column/row selection used by Theorem 8.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import selection as selection_lib
from repro.core import sketch as sk
from repro.core import sweep as sweep_lib
from repro.core.kernelop import SPSDOperator, as_operator
from repro.core.leverage import (column_leverage_scores_gram, pinv,
                                 row_leverage_scores, row_leverage_scores_gram)


def _shape_of(A) -> tuple:
    """(m, n) of a dense matrix or an implicit (square) ``SPSDOperator``."""
    if isinstance(A, SPSDOperator):
        return A.n, A.n
    return A.shape


def _rows_of(A, idx: jnp.ndarray) -> jnp.ndarray:
    """A[idx, :] without densifying an implicit operator."""
    if isinstance(A, SPSDOperator):
        return A.block(jnp.asarray(idx), jnp.arange(A.n))
    return jnp.take(A, idx, axis=0)


def _cols_of(A, idx: jnp.ndarray) -> jnp.ndarray:
    """A[:, idx] without densifying an implicit operator."""
    if isinstance(A, SPSDOperator):
        return A.columns(jnp.asarray(idx))
    return jnp.take(A, idx, axis=1)


def _block_of(A, ridx: jnp.ndarray, cidx: jnp.ndarray) -> jnp.ndarray:
    """A[ridx][:, cidx] — an (|ridx| × |cidx|) block."""
    if isinstance(A, SPSDOperator):
        return A.block(jnp.asarray(ridx), jnp.asarray(cidx))
    return jnp.take(jnp.take(A, ridx, axis=0), cidx, axis=1)


class CURApprox(NamedTuple):
    C: jnp.ndarray                 # (m, c)
    U: jnp.ndarray                 # (c, r)
    R: jnp.ndarray                 # (r, n)
    col_indices: Optional[jnp.ndarray] = None
    row_indices: Optional[jnp.ndarray] = None

    def dense(self) -> jnp.ndarray:
        return self.C @ self.U @ self.R


def select_cur_sketches(A, key: jax.Array, c: int, r: int,
                        selection="uniform", block_size: int = 1024,
                        mesh=None):
    """Sample the columns/rows forming C and R (the paper's §5.3 setup).

    ``A`` may be dense or an implicit ``SPSDOperator`` (kernel CUR): only the
    selected n×c / r×n panels are ever materialized.  ``selection`` names a
    registered ``SelectionPolicy`` (``repro.core.selection``); non-uniform
    policies need a square (SPSD) ``A`` — for an implicit operator the
    leverage/adaptive statistics stream through the operator protocol
    (blocked-Gram pilot leverage, ``ProjResidualColNorm`` sweeps), so C/R
    selection never materializes an O(n·r) intermediate beyond the C and R
    panels themselves.
    """
    kc, kr = jax.random.split(key)
    m, n = _shape_of(A)
    pol = selection_lib.get_policy(selection)
    if pol.name == "uniform":
        cidx = jax.random.choice(kc, n, shape=(c,), replace=False)
        ridx = jax.random.choice(kr, m, shape=(r,), replace=False)
    else:
        if m != n:
            raise ValueError(
                f"selection policy {pol.name!r} scores columns of a square "
                f"SPSD A; got shape {(m, n)} — use selection='uniform' for "
                f"rectangular matrices")
        # one call for both sides: policies with shareable scores (leverage
        # on a symmetric operator) pay for their pilot/scoring pass once
        cidx, ridx = pol.select_pair(as_operator(A), kc, c, r,
                                     block_size=block_size, mesh=mesh)
    return _cols_of(A, cidx), _rows_of(A, ridx), cidx, ridx


def optimal_U(A: jnp.ndarray, C: jnp.ndarray, R: jnp.ndarray) -> jnp.ndarray:
    return pinv(C) @ A.astype(jnp.float32) @ pinv(R)


def drineas08_U(A: jnp.ndarray, cidx: jnp.ndarray, ridx: jnp.ndarray) -> jnp.ndarray:
    """U = (P_R^T A P_C)†  — the poor-quality baseline of Fig. 2(c)."""
    W = jnp.take(jnp.take(A, ridx, axis=0), cidx, axis=1)   # (r, c)
    return pinv(W)                                           # (c, r)


def fast_U_cur(ScC: jnp.ndarray, ScASr: jnp.ndarray, RSr: jnp.ndarray) -> jnp.ndarray:
    """Ũ = (S_C^T C)† (S_C^T A S_R) (R S_R)†  (Eq. 9), products in f32 at
    ``Precision.HIGHEST``."""
    hi = jax.lax.Precision.HIGHEST
    return jnp.matmul(jnp.matmul(pinv(ScC), ScASr.astype(jnp.float32),
                                 precision=hi), pinv(RSr), precision=hi)


def blocked_right_sketch(A, S, block_size: int = 1024,
                         mesh=None) -> jnp.ndarray:
    """A S (m × s) streamed over row panels of A via the sweep engine.

    The dense route ``S.left(A.T).T`` stages an n×m transposed copy (and, for
    SRHT, a zero-padded one on top); sweeping row panels keeps the peak
    footprint at O(b·n + m·s) — the CUR analogue of the SPSD panel protocol —
    and a non-trivial ``mesh`` shards the panels across devices.

    An implicit ``SPSDOperator`` A routes through its own ``sweep``, so a
    Pallas-backed kernel claims matmul-shaped sketches with the fused
    (per-shard, rectangular-slab) launch.  For dense A under a non-trivial
    mesh, each shard claims its contiguous row slab through the engine's
    ``slab_fn`` hook — one ``S.right`` application per device instead of a
    panel scan — whenever the per-device slab stays inside the panel element
    budget (so the streaming memory story is preserved).
    """
    if isinstance(A, SPSDOperator):
        return sk.right_streaming(S, A, block_size=block_size, mesh=mesh)
    if isinstance(S, sk.GaussianSketch):
        return S.right(A)       # one GEMM; blocking would redraw S per block
    m, n = A.shape
    plan = sweep_lib.SketchRightPlan(S, S.s)
    dp = sweep_lib.mesh_data_size(mesh)
    slab_fn = None
    if dp > 1 and sweep_lib.local_slab_rows(m, n, block_size, dp) * n \
            <= sweep_lib.PANEL_ELEMENT_BUDGET:
        def slab_fn(row_idx, valid):
            slab = jnp.take(A, row_idx, axis=0)
            return (plan.update(plan.init(m, n), slab, row_idx, valid),)
    (AS,) = sweep_lib.sweep_panels(
        lambda idx: jnp.take(A, idx, axis=0), m, n, [plan],
        block_size=block_size, mesh=mesh, slab_fn=slab_fn)
    return AS


def fast_cur(
    A,
    key: jax.Array,
    c: int,
    r: int,
    sc: int,
    sr: int,
    sketch_kind: str = "leverage",
    enforce_subset: bool = True,
    scale: bool = False,
    streaming: bool = False,
    block_size: int = 1024,
    mesh=None,
    selection="uniform",
) -> CURApprox:
    """End-to-end fast CUR: select C/R, then the sketched Ũ (Thm 9 setup).

    ``selection`` picks WHICH columns/rows form C and R through the
    ``SelectionPolicy`` registry (uniform / leverage / uniform_adaptive2 /
    custom); for an implicit operator every policy statistic streams —
    leverage via the blocked-Gram pilot pass, adaptive residual norms via
    ``ProjResidualColNormPlan`` sweeps — adding exactly the policy's declared
    sweeps and nothing else to the PR 2/3 pass budget.
    Column-selection sketches observe only an (sc × sr) block of A plus C and R.
    Leverage sampling uses row scores of C (for S_C) and of R^T (for S_R).
    With ``streaming=True`` everything routes through the sweep engine:
    S_C^T A S_R via ``blocked_right_sketch`` (no transposed full-size
    temporaries), and the R-side leverage scores via the blocked Gram R Rᵀ
    pass (``column_leverage_scores_gram``) instead of densifying the n×r
    transpose — the path that survives n ≫ 10⁵.  ``mesh`` shards the sweeps
    (selection included).

    ``A`` may also be an implicit ``SPSDOperator`` (kernel CUR): every access
    goes through the operator protocol — C/R/blocks are gathered panels, and
    projection sketches stream through ``A.sweep``, where a Pallas-backed
    ``RBFKernel`` claims them with the fused (sharded) multi-RHS launch.
    Operators always take the streaming route; A is never densified.
    """
    is_op = isinstance(A, SPSDOperator)
    streaming = streaming or is_op
    m, n = _shape_of(A)
    kcr, kc, kr = jax.random.split(key, 3)
    C, R, cidx, ridx = select_cur_sketches(A, kcr, c, r, selection=selection,
                                           block_size=block_size, mesh=mesh)

    if sketch_kind in ("uniform", "leverage"):
        if sketch_kind == "leverage":
            if streaming:
                lev_c = row_leverage_scores_gram(C, block_size, mesh=mesh)
                lev_r = column_leverage_scores_gram(R, block_size, mesh=mesh)
            else:
                lev_c = row_leverage_scores(C)
                lev_r = row_leverage_scores(R.T)
            Sc = sk.leverage_column_sketch(kc, lev_c, sc, scale=scale)
            Sr = sk.leverage_column_sketch(kr, lev_r, sr, scale=scale)
        else:
            Sc = sk.uniform_column_sketch(kc, m, sc, scale=scale)
            Sr = sk.uniform_column_sketch(kr, n, sr, scale=scale)
        if enforce_subset:
            # §4.5 applied to CUR: rows selected by R ⊂ S_C, cols selected by C ⊂ S_R
            Sc = sk.subset_union_sketch(Sc, ridx, m)
            Sr = sk.subset_union_sketch(Sr, cidx, n)
        ScC = Sc.left(C)
        RSr = Sr.left(R.T).T
        blk = _block_of(A, Sc.indices, Sr.indices)
        ScASr = blk * (Sc.scales[:, None] * Sr.scales[None, :])
    else:
        Sc = sk.make_sketch(sketch_kind, kc, m, sc)
        Sr = sk.make_sketch(sketch_kind, kr, n, sr)
        ScC = Sc.left(C)
        RSr = Sr.left(R.T).T
        if streaming:
            ScASr = Sc.left(blocked_right_sketch(A, Sr, block_size, mesh=mesh))
        else:
            ScASr = Sc.left(Sr.left(A.T).T)

    U = fast_U_cur(ScC, ScASr, RSr)
    return CURApprox(C=C, U=U, R=R, col_indices=cidx, row_indices=ridx)


def optimal_cur(A: jnp.ndarray, key: jax.Array, c: int, r: int) -> CURApprox:
    C, R, cidx, ridx = select_cur_sketches(A, key, c, r)
    return CURApprox(C=C, U=optimal_U(A, C, R), R=R,
                     col_indices=cidx, row_indices=ridx)


# ---------------------------------------------------------------------------
# Adaptive row selection (Wang & Zhang 2013; used by Theorem 8)
# ---------------------------------------------------------------------------

def adaptive_row_indices(A: jnp.ndarray, base: jnp.ndarray, key: jax.Array,
                         extra: int) -> jnp.ndarray:
    """Sample ``extra`` rows ∝ squared residual norms against rows in ``base``."""
    R1 = jnp.take(A, base, axis=0)
    resid = A.astype(jnp.float32) - (A.astype(jnp.float32) @ pinv(R1)) @ R1.astype(jnp.float32)
    norms = jnp.sum(resid * resid, axis=1)
    p = norms / jnp.maximum(jnp.sum(norms), 1e-30)
    idx = jax.random.choice(key, A.shape[0], shape=(extra,), replace=True, p=p)
    return jnp.concatenate([base, idx])


def relative_error(A: jnp.ndarray, approx: CURApprox) -> jnp.ndarray:
    A32 = A.astype(jnp.float32)
    Rm = A32 - approx.dense().astype(jnp.float32)  # repro: allow-dense(CUR error oracle — A is already dense)
    return jnp.sum(Rm * Rm) / jnp.sum(A32 * A32)
