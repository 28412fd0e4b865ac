"""Plain references for the checks that decide ``correct``.

Straightforward ``jax.numpy`` and NumPy; nothing here imports the program.
Kernel entries come from the textbook formula, in row blocks so that an
O(n²) product fits the chip, and every contraction names its precision:

- ``"highest"``: float32 at ``Precision.HIGHEST``, what the configurations
  state;
- ``"high"``: the control, three bf16 passes (hi·hi + hi·lo + lo·hi),
  written out here so that it is the same arithmetic on every backend
  (XLA on the CPU ignores ``Precision.HIGH``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def _dot(a, b, **kw):
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32, **kw)


def _split(a: jnp.ndarray):
    """a = hi + lo with hi its top 8 significant bits (exactly a bfloat16)
    and lo the rest rounded to bfloat16.  The high half is cut by masking
    the bits, so that no compiler can fold a float32 → bfloat16 → float32
    round trip back into ``a`` and leave lo = 0."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def mm(a: jnp.ndarray, b: jnp.ndarray, precision: str) -> jnp.ndarray:
    """a @ b in float32 at the named precision."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return _dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        a_hi, a_lo = _split(a)
        b_hi, b_lo = _split(b)
        return _dot(a_hi, b_hi) + _dot(a_hi, b_lo) + _dot(a_lo, b_hi)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def sqdist(A: jnp.ndarray, B: jnp.ndarray, precision: str) -> jnp.ndarray:
    """‖a − b‖² for every pair, as |a|² + |b|² − 2 a·b."""
    aa = jnp.sum(A * A, axis=1)
    bb = jnp.sum(B * B, axis=1)
    return jnp.maximum(aa[:, None] + bb[None, :] - 2.0 * mm(A, B.T, precision),
                       0.0)


def rbf(A: jnp.ndarray, B: jnp.ndarray, sigma: float,
        precision: str) -> jnp.ndarray:
    """exp(−‖a − b‖² / (2σ²))."""
    return jnp.exp(sqdist(A, B, precision) * (-0.5 / (sigma * sigma)))


def row_block(n: int, cols: int, budget: int = 1 << 28) -> int:
    """Rows per block so that a (rows × cols) float32 block stays within
    ``budget`` elements (1 GiB by default)."""
    rows = max(1, budget // max(1, cols))
    return int(min(n, 1 << (rows.bit_length() - 1)))


@functools.partial(jax.jit, static_argnames=("sigma", "precision", "block"))
def _rbf_matmat(X, Y, V, sigma, precision, block):
    n, d = X.shape
    pad = (-n) % block
    Xb = jnp.pad(X, ((0, pad), (0, 0))).reshape(-1, block, d)
    out = jax.lax.map(lambda xb: mm(rbf(xb, Y, sigma, precision), V,
                                    precision), Xb)
    return out.reshape(-1, V.shape[1])[:n]


def rbf_matmat(X, Y, V, sigma: float, precision: str) -> jnp.ndarray:
    """K(X, Y) @ V, row block by row block."""
    block = row_block(X.shape[0], Y.shape[0])
    return _rbf_matmat(X, Y, V, float(sigma), precision, block)


@functools.partial(jax.jit, static_argnames=("sigma", "precision", "block"))
def _rbf_rows(X, Y, sigma, precision, block):
    n, d = X.shape
    pad = (-n) % block
    Xb = jnp.pad(X, ((0, pad), (0, 0))).reshape(-1, block, d)
    out = jax.lax.map(lambda xb: rbf(xb, Y, sigma, precision), Xb)
    return out.reshape(-1, Y.shape[0])[:n]


def rbf_rows(X, Y, sigma: float, precision: str) -> jnp.ndarray:
    """K(X, Y) as a dense (|X| × |Y|) array, row block by row block."""
    block = row_block(X.shape[0], Y.shape[0], budget=1 << 26)
    return _rbf_rows(X, Y, float(sigma), precision, block)


def pinv(A: jnp.ndarray, rcond: float, precision: str) -> jnp.ndarray:
    """Moore–Penrose inverse from a float32 SVD, singular values below
    ``rcond · max σ`` dropped."""
    u, s, vt = jnp.linalg.svd(A.astype(jnp.float32), full_matrices=False)
    inv = jnp.where(s > rcond * jnp.max(s), 1.0 / s, 0.0)
    return mm(vt.T * inv[None, :], u.T, precision)


def max_gap(a, b) -> float:
    """max |a − b| / max(1, max |b|), in float64."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))
