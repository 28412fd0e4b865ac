"""Downstream solvers on C U C^T (paper Appendix A).

These are what make the fast model useful: with (C, U) at hand the k-eigendecomposition
costs O(nc²) and the regularized solve O(nc²) (O(c³+nc) given the SVD of C).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp



class EigResult(NamedTuple):
    eigenvalues: jnp.ndarray    # (k,) descending
    eigenvectors: jnp.ndarray   # (n, k) orthonormal


def approx_eigh(C: jnp.ndarray, U: jnp.ndarray, k: int) -> EigResult:
    """Lemma 10: eigendecomposition of C U C^T in O(nc²).

    C = U_C Σ_C V_C^T;  Z = (Σ_C V_C^T) U (Σ_C V_C^T)^T = V_Z Λ V_Z^T;
    then C U C^T = (U_C V_Z) Λ (U_C V_Z)^T.
    """
    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    C32 = C.astype(jnp.float32)
    Uc, sc, Vct = jnp.linalg.svd(C32, full_matrices=False)
    M = mm(mm(sc[:, None] * Vct, U.astype(jnp.float32)), (sc[:, None] * Vct).T)
    M = 0.5 * (M + M.T)
    lam, Vz = jnp.linalg.eigh(M)                     # ascending
    lam = lam[::-1]
    Vz = Vz[:, ::-1]
    vecs = mm(Uc, Vz)
    return EigResult(eigenvalues=lam[:k], eigenvectors=vecs[:, :k])


def woodbury_solve(C: jnp.ndarray, U: jnp.ndarray, alpha: float,
                   y: jnp.ndarray) -> jnp.ndarray:
    """Lemma 11: solve (C U C^T + αIₙ) w = y in O(nc²).

    (CUC^T + αI)⁻¹ = α⁻¹ I − α⁻¹ C (α U⁻¹ + C^T C)⁻¹ C^T   (α>0, U SPSD).

    Implemented in the inverse-free form α U (α I + C^T C U)⁻¹ so singular U is
    fine (matches the Moore–Penrose limit used in the paper's experiments).

    Assumptions, validated up front:

    - ``alpha`` must be a strictly positive finite ridge: the identity
      divides by α, so α = 0 (or NaN/inf) produces NaN rows silently — an
      unregularized solve on a rank-deficient C U Cᵀ has no unique solution;
      use a pseudo-inverse route instead.
    - ``U`` must be SPSD (the fast/Nyström U matrices are, up to round-off):
      for indefinite U the inner α I + CᵀC U can be singular and the
      Woodbury identity itself no longer holds.

    A traced ``alpha`` (jit/vmap/grad over the ridge) cannot be validated at
    trace time and is passed through unchecked — the caller owns α > 0 there.
    """
    if not isinstance(alpha, jax.core.Tracer):
        a = float(alpha)
        if not (a > 0.0) or a == float("inf"):
            raise ValueError(
                f"woodbury_solve: alpha must be a finite positive ridge, "
                f"got {a!r}; the Woodbury identity divides by alpha and "
                f"would silently return NaN")
    C32 = C.astype(jnp.float32)
    U32 = U.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    CtC = C32.T @ C32
    c = C32.shape[1]
    # M = (α U^{-1} + C^T C)^{-1} = U (α I + C^T C U)^{-1}
    inner = alpha * jnp.eye(c, dtype=jnp.float32) + CtC @ U32
    M = U32 @ jnp.linalg.solve(inner, jnp.eye(c, dtype=jnp.float32))
    Cty = C32.T @ y32
    return (y32 - C32 @ (M @ Cty)) / alpha


def kpca_features(C: jnp.ndarray, U: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, EigResult]:
    """§6.3 KPCA: train features = Λ^{1/2} V^T  columns (returned as (n, k))."""
    eig = approx_eigh(C, U, k)
    lam = jnp.maximum(eig.eigenvalues, 0.0)
    feats = eig.eigenvectors * jnp.sqrt(lam)[None, :]
    return feats, eig


def kpca_transform(eig: EigResult, k_x: jnp.ndarray) -> jnp.ndarray:
    """Test features Λ^{-1/2} V^T k(x) for kernel column(s) k_x (n, b)."""
    lam = jnp.maximum(eig.eigenvalues, 1e-12)
    return (eig.eigenvectors.T @ k_x) / jnp.sqrt(lam)[:, None]


def misalignment(U_true: jnp.ndarray, V_approx: jnp.ndarray) -> jnp.ndarray:
    """Eq. 10: (1/k)||U_k − Ṽ Ṽ^T U_k||_F² ∈ [0, 1]."""
    k = U_true.shape[1]
    proj = V_approx @ (V_approx.T @ U_true)
    d = U_true - proj
    return jnp.sum(d * d) / k


def streaming_subspace_eigh(K, k: int, key=None, oversample: int = 8,
                            power_iters: int = 6, block_size=None,
                            mesh=None) -> EigResult:
    """Top-k eigenpairs of an SPSD *operator* by randomized subspace
    iteration (Halko–Martinsson–Tropp) — the exact-eigvec reference of the
    workload benches.

    Every application of K streams through ``matmat`` panel sweeps; the
    n×n kernel is never materialized.  ``power_iters+2`` sweeps total
    (probe, re-orthogonalized power steps, Rayleigh–Ritz), each a full
    multi-RHS pass over the operator.  Complements
    ``spsd.streaming_topk_eigvals`` (values only) with the eigenvector
    variant kernel-PCA misalignment needs.
    """
    from repro.core import spsd as spsd_lib
    from repro.core.kernelop import as_operator
    Kop = as_operator(K)
    if key is None:
        key = spsd_lib.default_probe_key()
    q = min(Kop.n, k + oversample)
    Y = Kop.matmat(jax.random.normal(key, (Kop.n, q), jnp.float32),
                   block_size=block_size, mesh=mesh)
    for _ in range(power_iters):
        Qb, _ = jnp.linalg.qr(Y)
        Y = Kop.matmat(Qb, block_size=block_size, mesh=mesh)
    Qb, _ = jnp.linalg.qr(Y)
    B = Qb.T @ Kop.matmat(Qb, block_size=block_size, mesh=mesh)
    B = 0.5 * (B + B.T)
    lam, W = jnp.linalg.eigh(B)                      # ascending
    lam = lam[::-1]
    W = W[:, ::-1]
    return EigResult(eigenvalues=lam[:k], eigenvectors=(Qb @ W)[:, :k])


def spectral_embedding(C: jnp.ndarray, U: jnp.ndarray, k: int,
                       eps: float = 1e-9,
                       degrees: jnp.ndarray | None = None) -> jnp.ndarray:
    """§6.4: normalized-Laplacian top-k eigenvectors from CUC^T ≈ K.

    d = CUC^T 1;  L = I − D^{-1/2} CUC^T D^{-1/2}; bottom-k of L = top-k of
    (D^{-1/2}C) U (D^{-1/2}C)^T — computed via Lemma 10. Rows are normalized.

    ``degrees`` substitutes *exact* degree sums d = K1 for the model-implied
    ones (one streamed ``matmat`` panel sweep on the kernel operator) — the
    degree-normalized route the spectral workload bench uses, so the
    normalization does not inherit the approximation's error.
    """
    ones = jnp.ones((C.shape[0], 1), C.dtype)
    d = ((C @ (U @ (C.T @ ones)))[:, 0] if degrees is None
         else degrees.astype(C.dtype))
    dinv = 1.0 / jnp.sqrt(jnp.maximum(d, eps))
    Cn = C * dinv[:, None]
    eig = approx_eigh(Cn, U, k)
    V = eig.eigenvectors
    norms = jnp.linalg.norm(V, axis=1, keepdims=True)
    return V / jnp.maximum(norms, eps)
