"""Pure-jnp oracles for the pairwise kernel sweep template.

One *independent* dense implementation per registered kernel — written from
the textbook formulas, NOT from ``KernelSpec.entry_fn`` — so the parity tests
check the spec definitions themselves, not just the Pallas plumbing around
them.  Small shapes only: every oracle materializes the full block.
"""
from __future__ import annotations
# repro: allow-file(RPR003: dense f32 oracles — operands are cast to f32 before every contraction)

import jax
import jax.numpy as jnp

from repro.kernels.pairwise.specs import KernelSpec


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """f32 product at full f32 precision, so the oracles hold on a TPU too
    (its default f32 matmul is one bf16 pass)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _sq(Xr: jnp.ndarray, Xc: jnp.ndarray) -> jnp.ndarray:
    Xr = Xr.astype(jnp.float32)
    Xc = Xc.astype(jnp.float32)
    rr = jnp.sum(Xr * Xr, axis=1)
    cc = jnp.sum(Xc * Xc, axis=1)
    return jnp.maximum(rr[:, None] + cc[None, :] - 2.0 * _mm(Xr, Xc.T), 0.0)


def rbf_block(Xr: jnp.ndarray, Xc: jnp.ndarray, sigma: float) -> jnp.ndarray:
    """K[ri, cj] = exp(-|x_ri - x_cj|^2 / (2 sigma^2)), f32 accumulation."""
    return jnp.exp(-_sq(Xr, Xc) / (2.0 * sigma ** 2))


def laplacian_block(Xr: jnp.ndarray, Xc: jnp.ndarray,
                    gamma: float) -> jnp.ndarray:
    """K[ri, cj] = exp(-gamma * ||x_ri - x_cj||_1) via the full broadcast."""
    Xr = Xr.astype(jnp.float32)
    Xc = Xc.astype(jnp.float32)
    l1 = jnp.sum(jnp.abs(Xr[:, None, :] - Xc[None, :, :]), axis=-1)
    return jnp.exp(-gamma * l1)


def matern32_block(Xr: jnp.ndarray, Xc: jnp.ndarray,
                   length_scale: float) -> jnp.ndarray:
    """K[ri, cj] = (1 + sqrt(3) r / l) exp(-sqrt(3) r / l), r = ||.||_2."""
    r = jnp.sqrt(_sq(Xr, Xc))
    z = (3.0 ** 0.5) * r / length_scale
    return (1.0 + z) * jnp.exp(-z)


def polynomial_block(Xr: jnp.ndarray, Xc: jnp.ndarray, degree: int = 3,
                     gamma: float | None = None,
                     coef0: float = 1.0) -> jnp.ndarray:
    """K[ri, cj] = (gamma x_ri . x_cj + coef0)^degree."""
    g = 1.0 if gamma is None else gamma
    dot = _mm(Xr.astype(jnp.float32), Xc.astype(jnp.float32).T)
    return (g * dot + coef0) ** degree


def linear_block(Xr: jnp.ndarray, Xc: jnp.ndarray) -> jnp.ndarray:
    """K[ri, cj] = x_ri . x_cj."""
    return _mm(Xr.astype(jnp.float32), Xc.astype(jnp.float32).T)


_ORACLES = {
    "rbf": rbf_block,
    "laplacian": laplacian_block,
    "matern32": matern32_block,
    "polynomial": polynomial_block,
    "linear": linear_block,
}


def kernel_block(spec: KernelSpec, Xr: jnp.ndarray,
                 Xc: jnp.ndarray) -> jnp.ndarray:
    """Dispatch to the named oracle with the spec's parameters."""
    if spec.name not in _ORACLES:
        raise KeyError(f"no ref oracle for kernel {spec.name!r}; known: "
                       f"{tuple(sorted(_ORACLES))}")
    return _ORACLES[spec.name](Xr, Xc, **dict(spec.params))


def kernel_matmat_multi_rows(spec: KernelSpec, Xr: jnp.ndarray,
                             Xc: jnp.ndarray, Vs):
    """Rectangular row-slab oracle: [K(Xr, Xc) @ V for V in Vs]."""
    K = kernel_block(spec, Xr, Xc)
    return tuple(_mm(K, V.astype(jnp.float32)) for V in Vs)


def kernel_matmat(spec: KernelSpec, X: jnp.ndarray,
                  V: jnp.ndarray) -> jnp.ndarray:
    """K(X, X) @ V oracle (materializes K — small shapes only)."""
    return _mm(kernel_block(spec, X, X), V.astype(jnp.float32))
