"""Landmark (sketched) attention — the paper's fast CUR applied to attention.

Full attention computes ``softmax(QK^T/√d) V``.  Write ``G = exp(QK^T/√d)``
(m × n, entrywise-positive Gram-like matrix).  Then

    out = (G V) / (G 1).

We approximate G once with the paper's fast CUR (Eq. 9) and reuse the factors
for both the numerator and the normalizer:

    G ≈ Ĉ Ũ R̂,   Ĉ = exp(Q K_P^T/√d) (m×c),   R̂ = exp(Q_P K^T/√d) (c×n),
    Ũ = (S_q^T Ĉ)† (S_q^T G S_k) (R̂ S_k)†        — fast-CUR U, s = θ·c.

``P`` are c landmark positions; sketches satisfy P ⊂ S (§4.5).  Plain
Nyströmformer is the degenerate S = P case (exactly the paper's reading of
Nyström as a crude sketched solve); the prototype-quality solve is S = I.

Cost: O(m·c + n·c + s²c) instead of O(m·n) — sub-quadratic for s = O(c√(n/ε)).
For autoregressive decode the factors ``Ũ (R̂ V)`` and ``Ũ (R̂ 1)`` are
cached (c×d_v and c×1) beside Ũ, making per-token cost O(c·d + c²): each
decoded key and value is folded in as one more column of R̂ (the sketch and
Ũ stay as built at prefill), and the query reads the updated factors — one
Pallas launch, ``kernels/landmark_attention``.

Landmark positions default to strided-with-jitter (``selection="strided"``),
but any registered :class:`~repro.core.selection.SelectionPolicy` name picks
landmarks from the context's own softmax Gram ``exp(K Kᵀ/√d)`` — the same
streaming column-selection machinery the SPSD models use (leverage /
adaptive² landmarks, every kernel access through the operator protocol).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.cur import fast_U_cur
from repro.core.leverage import pinv


class LandmarkState(NamedTuple):
    """Decode-time cache: everything that depends only on the context K/V."""
    k_land: jnp.ndarray    # (c, d)   landmark keys
    U: jnp.ndarray         # (c, c)   the fast-CUR Ũ, to fold new columns
    UV: jnp.ndarray        # (c, d_v) Ũ @ (R̂ V)
    U1: jnp.ndarray        # (c,)     Ũ @ (R̂ 1)
    scale: jnp.ndarray     # ()       max-logit offset used inside exp


HIGHEST = jax.lax.Precision.HIGHEST


def _logits(Q: jnp.ndarray, K: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(Q.astype(jnp.float32), K.astype(jnp.float32).T,
                      precision=HIGHEST)


def _exp_scores(Q: jnp.ndarray, K: jnp.ndarray, inv_sqrt_d: float,
                offset: jnp.ndarray) -> jnp.ndarray:
    return jnp.exp(_logits(Q, K) * inv_sqrt_d - offset)


def signed_den_floor(den: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Floor ``|den|`` at ``eps`` *preserving sign*.

    The normalizer ``Ĉ Ũ R̂ 1`` can go negative through an indefinite fast/
    Nyström ``Ũ`` even though the exact ``G 1`` is positive; a plain
    ``maximum(den, eps)`` silently flips the sign of the whole output row.
    Keeping the sign makes ``num/den`` invariant to a global sign flip of
    ``Ũ`` (both factors flip) and only guards against division blow-up.
    """
    return jnp.where(den < 0.0, -1.0, 1.0) * jnp.maximum(jnp.abs(den), eps)


def landmark_indices(key: jax.Array, n: int, c: int) -> jnp.ndarray:
    """Uniform landmarks (paper §6: uniform ≈ leverage for S; C uniform).

    Strided base + per-segment jitter gives c *distinct* positions for
    c < n.  A request of c >= n landmarks is degenerate (the old
    ``seg = n // c == 0`` path collapsed every index to 0): clamp to all n
    positions, distinct, with a warning.
    """
    if c >= n:
        if c > n:
            warnings.warn(
                f"landmark_indices: requested c={c} >= n={n}; clamping to "
                "all n distinct positions", stacklevel=2)
        return jax.random.permutation(key, n)
    seg = n // c
    base = jnp.arange(c) * seg
    jitter = jax.random.randint(key, (c,), 0, max(seg, 1))
    return jnp.clip(base + jitter, 0, n - 1)


@functools.lru_cache(maxsize=None)
def _softmax_gram_spec(inv_sqrt_d: float, offset: float):
    """Unregistered KernelSpec for the context softmax Gram exp(KKᵀ/√d − off).

    Built directly (NOT through ``register_kernel``) so the conformance /
    parity suites that parametrize over ``registered_kernels()`` are
    unaffected; cached per (scale, offset) because specs hash by field
    identity, keeping one jit entry per parameter set.
    """
    from repro.kernels.pairwise.specs import KernelSpec
    return KernelSpec(
        "softmax_gram", "dot",
        lambda t: jnp.exp(t * inv_sqrt_d - offset),
        params=(("inv_sqrt_d", inv_sqrt_d), ("offset", offset)))


def select_landmarks(K: jnp.ndarray, key: jax.Array, c: int,
                     selection: str = "strided",
                     block_size: int | None = None) -> jnp.ndarray:
    """Pick c landmark key positions.

    ``"strided"`` is the classic Nyströmformer layout
    (:func:`landmark_indices`).  Any other name resolves through the
    :mod:`repro.core.selection` registry and selects columns of the
    context's softmax Gram operator ``exp(K Kᵀ/√d − offset)`` — an SPSD
    ``PairwiseKernel`` with an (unregistered) exp-dot spec, so leverage /
    adaptive² landmark choice streams through the sweep engine exactly like
    the kernel models (no n×n materialization).
    """
    n, d = K.shape
    if selection == "strided":
        return landmark_indices(key, n, c)
    from repro.core import selection as selection_lib
    from repro.core.kernelop import PairwiseKernel
    inv_sqrt_d = 1.0 / float(d) ** 0.5
    if isinstance(K, jax.core.Tracer):
        offset = 0.0                      # traced context: no concrete max
    else:                                 # stabilize exp: diag logits <= 0
        offset = round(
            float(jnp.max(jnp.sum(K.astype(jnp.float32) ** 2, axis=1)))
            * inv_sqrt_d, 3)
    spec = _softmax_gram_spec(inv_sqrt_d, offset)
    op = PairwiseKernel(K.astype(jnp.float32), spec)
    policy = selection_lib.get_policy(selection)
    return policy.select(op, key, min(c, n), block_size=block_size)


def _extend_without_replacement(key: jax.Array, base: jnp.ndarray, s: int,
                                n: int) -> jnp.ndarray:
    """``base`` plus (s − |base|) distinct indices from its complement.

    The sketch sets must be duplicate-free: sampling the extension with
    replacement (or without excluding ``base``) lands repeated rows in
    ``S_qᵀĈ`` / ``R̂ S_k``, biasing the fast-CUR solve exactly like the PR-5
    with-replacement adaptive-sampling bug.
    """
    extra = s - base.shape[0]
    if extra <= 0:
        return base[:s]
    w = jnp.ones((n,), jnp.float32).at[base].set(0.0)
    ext = jax.random.choice(key, n, (extra,), replace=False, p=w / jnp.sum(w))
    return jnp.concatenate([base, ext])


def _sketch_indices(kq: jax.Array, kk: jax.Array, p_idx: jnp.ndarray,
                    m: int, n: int, c: int,
                    theta: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row (queries) and column (keys) sketch index sets for Eq. 9.

    The column sketch always extends the landmarks (P ⊂ S, §4.5) with
    distinct non-landmark columns.  The row sketch mirrors it when the Gram
    is square (m == n); for rectangular attention it is a plain
    without-replacement row sample of [0, m) — the old code gathered
    ``arange(c)`` rows of Q there, which clamp-duplicates out-of-bounds rows
    whenever m < c.
    """
    s_k = min(theta * c, n)
    skx = _extend_without_replacement(kk, p_idx, s_k, n)
    if m == n:
        sq = _extend_without_replacement(kq, p_idx, s_k, m)
    else:
        s_q = min(theta * c, m)
        sq = jax.random.choice(kq, m, (s_q,), replace=False)
    return sq, skx


def sketched_attention(
    Q: jnp.ndarray,               # (m, d)
    K: jnp.ndarray,               # (n, d)
    V: jnp.ndarray,               # (n, d_v)
    key: jax.Array,
    c: int,
    theta: int = 4,               # s = θ·c, paper's Fig. 3/4 sweep
    mode: str = "fast",           # fast | nystrom | prototype
    selection: str = "strided",   # or any SelectionPolicy registry name
) -> jnp.ndarray:
    """Non-causal sketched attention over a full context."""
    m, d = Q.shape
    n = K.shape[0]
    inv_sqrt_d = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    kp, kq, kk = jax.random.split(key, 3)

    p_idx = select_landmarks(K, kp, c, selection=selection)
    c = p_idx.shape[0]            # may have been clamped to n
    Kp = jnp.take(K, p_idx, axis=0)
    Qp = jnp.take(Q, p_idx, axis=0) if m == n else jnp.take(K, p_idx, axis=0)

    # stabilization offset: max landmark logit (global max is close for RBF-ish G)
    offset = jnp.max(_logits(Qp, Kp)) * inv_sqrt_d

    Chat = _exp_scores(Q, Kp, inv_sqrt_d, offset)       # (m, c)
    Rhat = _exp_scores(Qp, K, inv_sqrt_d, offset)       # (c, n)

    if mode == "prototype":                              # S = I (exact solve)
        G = _exp_scores(Q, K, inv_sqrt_d, offset)
        U = pinv(Chat) @ G @ pinv(Rhat)
    elif mode == "nystrom":                              # S = P
        W = _exp_scores(Qp, Kp, inv_sqrt_d, offset)
        U = pinv(W)
    else:                                                # fast CUR (Eq. 9)
        sq, skx = _sketch_indices(kq, kk, p_idx, m, n, c, theta)
        ScC = jnp.take(Chat, sq, axis=0)                 # (s_q, c)
        RSr = jnp.take(Rhat, skx, axis=1)                # (c, s_k)
        G_blk = _exp_scores(jnp.take(Q, sq, axis=0),
                            jnp.take(K, skx, axis=0), inv_sqrt_d, offset)
        U = fast_U_cur(ScC, G_blk, RSr)

    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    num = mm(Chat, mm(U, mm(Rhat, V.astype(jnp.float32))))   # (m, d_v)
    den = mm(Chat, mm(U, jnp.sum(Rhat, axis=1)))             # (m,)
    den = signed_den_floor(den)[:, None]
    return (num / den).astype(V.dtype)


# ---------------------------------------------------------------------------
# Decode path: O(c) per token against a 500k context
# ---------------------------------------------------------------------------

def build_landmark_state(K: jnp.ndarray, V: jnp.ndarray, key: jax.Array,
                         c: int, theta: int = 4,
                         selection: str = "strided") -> LandmarkState:
    """Precompute the context-side factors once (prefill), in f32."""
    n, d = K.shape
    K = K.astype(jnp.float32)
    inv_sqrt_d = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    kp, ks = jax.random.split(key)
    p_idx = select_landmarks(K, kp, c, selection=selection)
    c = p_idx.shape[0]            # may have been clamped to n
    Kp = jnp.take(K, p_idx, axis=0)
    offset = jnp.max(_logits(Kp, Kp)) * inv_sqrt_d

    Rhat = _exp_scores(Kp, K, inv_sqrt_d, offset)        # (c, n)
    s = min(theta * c, n)
    skx = _extend_without_replacement(ks, p_idx, s, n)
    # queries at the sketched rows are the landmark keys themselves (self-Gram)
    Ks = jnp.take(K, skx, axis=0)
    ScC = _exp_scores(Ks, Kp, inv_sqrt_d, offset)
    G_blk = _exp_scores(Ks, Ks, inv_sqrt_d, offset)
    RSr = jnp.take(Rhat, skx, axis=1)
    U = fast_U_cur(ScC, G_blk, RSr)

    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    RV = mm(Rhat, V.astype(jnp.float32))                 # (c, d_v)
    R1 = jnp.sum(Rhat, axis=1)                           # (c,)
    return LandmarkState(k_land=Kp, U=U, UV=mm(U, RV), U1=mm(U, R1),
                         scale=offset)


def landmark_decode(state: LandmarkState, q: jnp.ndarray) -> jnp.ndarray:
    """One-token attention read: (d,) query -> (d_v,) output, O(c·d), through
    the landmark-attention launch."""
    from repro.kernels.landmark_attention import ops as lm_ops
    return lm_ops.landmark_read(q[None], state.k_land, state.UV, state.U1,
                                state.scale)[0]
