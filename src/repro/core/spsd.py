"""SPSD matrix approximation models (paper §3.2 & §4).

All three models produce ``K ≈ C U C^T`` with the same sketch ``C = K P`` and
differ only in U (Table 1):

- prototype:  U* = C† K (C†)^T                    O(n²c), sees all of K
- Nyström:    U  = (P^T K P)†                      O(c³),  sees n·c entries
- fast:       U  = (S^T C)† (S^T K S) (C^T S)†     O(nc² + s²c), nc + (s-c)² entries

``fast_spsd`` is Algorithm 1 end-to-end (with the §4.5 tricks: P ⊂ S and
unscaled leverage sampling by default).

Every large-n path streams through the single-sweep panel engine
(``SPSDOperator.sweep`` / ``matmat``): ``fast_model`` gathers C = K P and
applies the projection sketch from ONE pass over the kernel row panels, and
``fast_model_with_error`` folds the Hutchinson error probes into the same
pass — model + error for one evaluation of each kernel entry, the Table-3
"#Entries" economy at its floor.  Pass ``mesh=`` (a Mesh with a ``data``
axis, see ``distributed/sharding.py``) to shard every sweep across devices.
``fast_model_batched`` vmaps Algorithm 1 over a stacked batch of kernels;
ragged batches are handled by ``n_valid`` padding masks.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import selection as selection_lib
from repro.core import sketch as sk
from repro.core import sweep as sweep_lib
from repro.core.instrument import span
from repro.core.kernelop import DenseSPSD, SPSDOperator, as_operator
from repro.core.leverage import pinv, row_leverage_scores

# Below this n the dense error metrics are cheap and exact; above it the
# "auto" policy switches to the streaming estimators.
_DENSE_N_CUTOFF = 2048


def _mm(a, b):
    """f32 product at full f32 precision (a TPU's default f32 matmul is one
    bf16 pass, ~1e-3 relative)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# Seed used when a randomized estimator (Hutchinson probes, subspace
# iteration) is called with ``key=None``.  Deliberate and documented: the
# default path is deterministic across runs/processes so error trajectories
# are comparable, and callers who want fresh probes pass an explicit key —
# see the regression test that two distinct keys give distinct estimates.
DEFAULT_PROBE_SEED = 0


def default_probe_key() -> jax.Array:
    """The documented deterministic key for ``key=None`` estimator calls."""
    return jax.random.PRNGKey(DEFAULT_PROBE_SEED)


class SPSDApprox(NamedTuple):
    """K ≈ C U C^T."""
    C: jnp.ndarray          # (n, c)
    U: jnp.ndarray          # (c, c)
    P_indices: Optional[jnp.ndarray] = None   # columns of K forming C (if sampled)

    def dense(self) -> jnp.ndarray:
        return _mm(_mm(self.C, self.U), self.C.T)

    def matmat(self, V: jnp.ndarray) -> jnp.ndarray:
        return _mm(self.C, _mm(self.U, _mm(self.C.T, V)))


# ---------------------------------------------------------------------------
# U matrices
# ---------------------------------------------------------------------------

def prototype_U(K, C: jnp.ndarray, block_size: Optional[int] = None,
                mesh=None) -> jnp.ndarray:
    """U* = argmin_U ||K - C U C^T||_F = C† K (C†)^T  (Eq. 4).

    K may be dense or any ``SPSDOperator``; K (C†)^T is streamed through
    ``matmat`` (one panel sweep, shardable via ``mesh``) so implicit kernels
    are never densified.
    """
    Kop = as_operator(K)
    Cp = pinv(C)                                          # (c, n) f32
    KCpT = Kop.matmat(Cp.T, block_size=block_size, mesh=mesh)  # (n, c)
    return Cp @ KCpT.astype(Cp.dtype)


def nystrom_U(W: jnp.ndarray) -> jnp.ndarray:
    """U^nys = W† with W = P^T K P (Eq. 3)."""
    Wsym = 0.5 * (W + W.T)
    return pinv(Wsym)


@span("spsd.fast_u")
def fast_U(StC: jnp.ndarray, StKS: jnp.ndarray) -> jnp.ndarray:
    """U^fast = (S^T C)† (S^T K S) (C^T S)†  (Eq. 5).

    StC: (s, c), StKS: (s, s).  Cost O(s²c) — independent of n.
    """
    StCp = pinv(StC)                      # (c, s)
    return _mm(_mm(StCp, StKS.astype(StCp.dtype)), StCp.T)


# ---------------------------------------------------------------------------
# End-to-end models
# ---------------------------------------------------------------------------

def sample_C(Kop: SPSDOperator, key: jax.Array, c: int) -> SPSDApprox:
    """Uniformly sample c columns of K to form C (the sketch this paper fixes)."""
    idx = jax.random.choice(key, Kop.n, shape=(c,), replace=False)
    C = Kop.columns(idx)
    return SPSDApprox(C=C, U=jnp.eye(c, dtype=C.dtype), P_indices=idx)


def prototype_model(K, C: jnp.ndarray, P_indices=None,
                    block_size: Optional[int] = None) -> SPSDApprox:
    Kop = as_operator(K)
    U = prototype_U(Kop, C, block_size=block_size)
    return SPSDApprox(C=C, U=U, P_indices=P_indices)


def nystrom_model(K, key: jax.Array, c: int) -> SPSDApprox:
    Kop = as_operator(K)
    idx = jax.random.choice(key, Kop.n, shape=(c,), replace=False)
    C = Kop.columns(idx)
    W = Kop.block(idx, idx)
    return SPSDApprox(C=C, U=nystrom_U(W), P_indices=idx)


@span("spsd.sketch_block")
def _column_sketch_for_C(Kop: SPSDOperator, C: jnp.ndarray, key: jax.Array,
                         s: int, s_sketch: str, P_indices, enforce_subset: bool,
                         scale: bool, mask: Optional[jnp.ndarray]):
    """The uniform/leverage S plus its S^T K S block (s² entries, no sweep)."""
    n = Kop.n
    if s_sketch == "leverage":
        # padding rows of a masked C are exactly zero -> leverage 0 -> never
        # sampled, so no extra masking is needed here.
        lev = row_leverage_scores(C)
        S = sk.leverage_column_sketch(key, lev, s, scale=scale)
    else:
        S = sk.uniform_column_sketch(key, n, s, scale=scale, mask=mask)
    if enforce_subset and P_indices is not None:
        S = sk.subset_union_sketch(S, P_indices, n)         # Corollary 5
    StC = S.left(C)
    blk = Kop.block(S.indices, S.indices)
    StKS = blk * (S.scales[:, None] * S.scales[None, :])
    return S, StC, StKS


def fast_model_from_C(
    K,
    C: jnp.ndarray,
    key: jax.Array,
    s: int,
    P_indices: Optional[jnp.ndarray] = None,
    s_sketch: str = "leverage",
    enforce_subset: bool = True,
    scale: bool = False,
    streaming: Optional[bool] = None,
    block_size: Optional[int] = None,
    mesh=None,
    n_valid=None,
) -> SPSDApprox:
    """Algorithm 1 given a fixed C (any provenance).

    ``s_sketch`` ∈ {uniform, leverage, gaussian, srht, countsketch}.
    Column-selection sketches read only an s×s block of K (Fig. 1).
    Projection sketches form S^T K S through one panel sweep
    (``sketch.sym_streaming``, shardable via ``mesh``) unless
    ``streaming=False`` forces the dense route; default is streaming for
    every implicit operator, dense only for an already-materialized
    ``DenseSPSD``.  ``n_valid`` marks the true size of a padded operator
    (rows ≥ n_valid are masked out of every product).
    """
    Kop = as_operator(K)
    n = Kop.n
    mask = None if n_valid is None else \
        (jnp.arange(n) < n_valid).astype(jnp.float32)

    if s_sketch in ("uniform", "leverage"):
        _, StC, StKS = _column_sketch_for_C(
            Kop, C, key, s, s_sketch, P_indices, enforce_subset, scale, mask)
    else:
        S = sk.make_sketch(s_sketch, key, n, s)
        if mask is not None:
            S = sk.MaskedSketch(S, mask)
        StC = S.left(C)
        if streaming is None:
            streaming = not isinstance(Kop, DenseSPSD)
        if streaming:
            StKS = sk.sym_streaming(S, Kop, block_size=block_size, mesh=mesh)
        else:
            StKS = S.sym(Kop.full())  # repro: allow-dense(caller forced streaming=False — explicit dense opt-out)

    U = fast_U(StC, StKS)
    return SPSDApprox(C=C, U=U, P_indices=P_indices)


def fast_model(
    K,
    key: jax.Array,
    c: int,
    s: int,
    s_sketch: str = "leverage",
    enforce_subset: bool = True,
    scale: bool = False,
    streaming: Optional[bool] = None,
    block_size: Optional[int] = None,
    mesh=None,
    n_valid=None,
    selection="uniform",
) -> SPSDApprox:
    """Algorithm 1 end-to-end: select C = KP columns, then the fast U.

    ``selection`` names a registered ``SelectionPolicy`` (``uniform``,
    ``leverage``, ``uniform_adaptive2``, or a policy instance) that picks
    WHICH columns form C; every policy meets a declared kernel-sweep budget
    and streams through the operator protocol (``repro.core.selection``).
    With a projection ``s_sketch`` on a streaming operator, the C gather and
    the K @ S product ride the SAME panel sweep — every kernel row panel is
    evaluated exactly once for the whole model.  On the fused Pallas route
    that sweep is one launch, which computes C's n·c entries from the
    selected points beside the K @ S contraction (C takes no right-hand
    side and no extra pass over X).  ``mesh`` shards every sweep the model
    AND the selection policy run; ``n_valid`` handles padded (ragged-batch)
    operators — the mask restricts the policy to valid rows too.
    """
    Kop = as_operator(K)
    n = Kop.n
    kc, ks = jax.random.split(key)
    mask = None if n_valid is None else \
        (jnp.arange(n) < n_valid).astype(jnp.float32)
    pol = selection_lib.get_policy(selection)
    idx = pol.select(Kop, kc, c, block_size=block_size, mesh=mesh, mask=mask)

    if streaming is None:
        streaming = not isinstance(Kop, DenseSPSD)
    if s_sketch in ("uniform", "leverage") or not streaming:
        C = Kop.columns(idx)
        if mask is not None:
            C = C * mask[:, None]
        return fast_model_from_C(
            Kop, C, ks, s,
            P_indices=idx, s_sketch=s_sketch,
            enforce_subset=enforce_subset, scale=scale,
            streaming=streaming, block_size=block_size, mesh=mesh,
            n_valid=n_valid)

    # fused path: C = K P and K S from ONE sweep over the row panels
    S = sk.make_sketch(s_sketch, ks, n, s)
    if mask is not None:
        S = sk.MaskedSketch(S, mask)
    C, KS = Kop.sweep(
        [sweep_lib.ColumnGatherPlan(idx), sk.plan_for_sketch(S)],
        block_size=block_size, mesh=mesh)
    if mask is not None:
        C = C * mask[:, None]
    U = fast_U(S.left(C), S.left(KS))
    return SPSDApprox(C=C, U=U, P_indices=idx)


def fast_model_with_error(
    K,
    key: jax.Array,
    c: int,
    s: int,
    s_sketch: str = "gaussian",
    probes: int = 64,
    enforce_subset: bool = True,
    scale: bool = False,
    block_size: Optional[int] = None,
    mesh=None,
    error_key: Optional[jax.Array] = None,
    selection="uniform",
) -> Tuple[SPSDApprox, jnp.ndarray]:
    """Algorithm 1 + its Hutchinson relative error in ONE panel sweep.

    The error probes Z are independent of the model, so K @ Z joins the same
    sweep that gathers C and applies the projection sketch: the whole
    model-plus-evaluation pipeline reads each kernel row panel exactly once.
    On the fused Pallas route (both sketch branches) the sweep is one
    launch: it contracts each kernel tile against the probes (and the
    projection sketch) and computes C's n·c entries from the landmark
    points X[idx] in the same pass.  ``selection`` picks the policy that chooses
    C's columns (its declared sweeps are the only addition to the budget).
    Returns ``(approx, relative_error)`` with the same estimator as
    ``relative_error(method="hutchinson")``.  Its phases run inside the
    spans ``spsd.select``, ``sweep.<route>``, ``spsd.sketch_block``,
    ``spsd.fast_u`` and ``spsd.certify`` (``instrument.span``).
    """
    Kop = as_operator(K)
    n = Kop.n
    with span("spsd.select"):
        kc, ks = jax.random.split(key)
        kz = jax.random.fold_in(key, 777) if error_key is None else error_key
        pol = selection_lib.get_policy(selection)
        idx = pol.select(Kop, kc, c, block_size=block_size, mesh=mesh)
        Z = jax.random.rademacher(kz, (n, probes), dtype=jnp.float32)

    if s_sketch in ("uniform", "leverage"):
        C, KZ = Kop.sweep(
            [sweep_lib.ColumnGatherPlan(idx), sweep_lib.MatmulPlan(Z)],
            block_size=block_size, mesh=mesh)
        _, StC, StKS = _column_sketch_for_C(
            Kop, C, ks, s, s_sketch, idx, enforce_subset, scale, None)
    else:
        S = sk.make_sketch(s_sketch, ks, n, s)
        C, KS, KZ = Kop.sweep(
            [sweep_lib.ColumnGatherPlan(idx), sk.plan_for_sketch(S),
             sweep_lib.MatmulPlan(Z)],
            block_size=block_size, mesh=mesh)
        with span("spsd.sketch_block"):
            StC, StKS = S.left(C), S.left(KS)

    approx = SPSDApprox(C=C, U=fast_U(StC, StKS), P_indices=idx)
    with span("spsd.certify"):
        RZ = KZ.astype(jnp.float32) - approx.matmat(Z).astype(jnp.float32)
        err = jnp.sum(RZ * RZ) / jnp.sum(KZ * KZ)
    return approx, err


def fast_model_batched(
    Ks,
    keys: jax.Array,
    c: int,
    s: int,
    s_sketch: str = "leverage",
    enforce_subset: bool = True,
    scale: bool = False,
    streaming: Optional[bool] = None,
    block_size: Optional[int] = None,
    n_valid: Optional[jnp.ndarray] = None,
    selection="uniform",
) -> SPSDApprox:
    """Algorithm 1 vmapped over a batch of kernels.

    ``Ks`` is one operator pytree whose leaves carry a leading batch axis —
    e.g. ``RBFKernel(X_batch)`` with ``X_batch`` of shape (B, n, d), or
    ``DenseSPSD(K_batch)`` with (B, n, n) — and ``keys`` has shape (B, 2) as
    produced by ``jax.random.split``.  Returns an ``SPSDApprox`` whose fields
    are stacked along the batch axis.  Whole-batch work runs in one XLA
    computation, so many moderate kernels (hyperparameter sweeps, per-class
    Gram matrices) amortize compilation and saturate the accelerator.
    ``selection`` picks the C-column policy per item (the whole policy —
    pilot gathers, residual-norm sweeps — traces under the vmap).

    Ragged batches: zero-pad each kernel's data to a common n and pass
    ``n_valid`` of shape (B,) with the true sizes.  Sampling is restricted to
    valid rows, C's padding rows are zeroed, and projection sketches are
    row-masked (``sketch.MaskedSketch``), so Sᵀ K S never observes a padding
    entry and the per-item results match unpadded runs.  ``fast_model_ragged``
    adds automatic size-bucketing on top so wildly mixed sizes don't all pay
    the largest item's padding.
    """
    if not isinstance(Ks, SPSDOperator):
        Ks = DenseSPSD(jnp.asarray(Ks))

    def one(op, key, nv):
        return fast_model(op, key, c=c, s=s, s_sketch=s_sketch,
                          enforce_subset=enforce_subset, scale=scale,
                          streaming=streaming, block_size=block_size,
                          n_valid=nv, selection=selection)

    if n_valid is None:
        return jax.vmap(lambda op, key: one(op, key, None))(Ks, keys)
    return jax.vmap(one)(Ks, keys, jnp.asarray(n_valid))


def bucket_by_size(sizes, waste: float = 0.25):
    """Greedy size-bucketing for ragged batches: index groups whose padded
    height stays within ``(1 + waste)×`` each member's true size.

    Items are visited in descending size order and join the current bucket
    while the bucket's padded height (its largest member) costs them at most
    a ``waste`` fraction of padding rows; otherwise a new bucket opens.  So
    every item's padding overhead is bounded by ``waste`` and the number of
    vmapped computations stays minimal for that bound.
    """
    order = sorted(range(len(sizes)), key=lambda i: -int(sizes[i]))
    buckets, cur, cap = [], [], 0
    for i in order:
        n_i = int(sizes[i])
        if cur and cap > n_i * (1.0 + waste):
            buckets.append(cur)
            cur = []
        if not cur:
            cap = n_i
        cur.append(i)
    if cur:
        buckets.append(cur)
    return buckets


def fast_model_ragged(
    Xs,
    make_operator,
    keys: jax.Array,
    c: int,
    s: int,
    waste: float = 0.25,
    **kwargs,
):
    """Algorithm 1 over a ragged list of datasets with automatic bucketing.

    ``Xs`` is a list of (n_i, d) data arrays (different n_i), and
    ``make_operator`` maps a stacked (B, n_pad, d) array to a batched
    operator pytree (e.g. ``lambda Xb: RBFKernel(Xb, sigma=1.5)``).  Items
    are grouped by ``bucket_by_size(..., waste)``, zero-padded only to their
    bucket's height, and each bucket runs one ``fast_model_batched`` call
    with the true sizes as ``n_valid`` — bounding padding waste at ``waste``
    instead of padding everything to the global maximum.  Extra ``kwargs``
    (``s_sketch``, ``selection``, …) pass through.  Returns a list of
    per-item ``SPSDApprox`` with C trimmed back to each item's true n,
    ordered like ``Xs``.
    """
    sizes = [int(x.shape[0]) for x in Xs]
    out = [None] * len(Xs)
    for bucket in bucket_by_size(sizes, waste):
        npad = max(sizes[i] for i in bucket)
        Xb = jnp.stack([jnp.pad(jnp.asarray(Xs[i]),
                                ((0, npad - sizes[i]), (0, 0)))
                        for i in bucket])
        kb = jnp.stack([keys[i] for i in bucket])
        nv = jnp.asarray([sizes[i] for i in bucket])
        bat = fast_model_batched(make_operator(Xb), kb, c=c, s=s,
                                 n_valid=nv, **kwargs)
        for j, i in enumerate(bucket):
            P = None if bat.P_indices is None else bat.P_indices[j]
            out[i] = SPSDApprox(C=bat.C[j][: sizes[i]], U=bat.U[j],
                                P_indices=P)
    return out


# ---------------------------------------------------------------------------
# Error metrics used throughout the paper's §6
#
# Three evaluation methods, selected by ``method``:
#   dense       exact, materializes K — small n only.
#   blocked     exact, accumulates ||K - CUC^T||_F² over row panels; O(b·n)
#               memory, reads each kernel entry once.
#   hutchinson  stochastic: ||R||_F² = E_z ||R z||² over Rademacher probes;
#               one streaming K @ Z pass serves numerator and denominator.
#   auto        dense below _DENSE_N_CUTOFF (or for DenseSPSD), else blocked.
# ---------------------------------------------------------------------------

def _resolve_error_method(Kop: SPSDOperator, method: str) -> str:
    if method != "auto":
        return method
    if isinstance(Kop, DenseSPSD) or Kop.n <= _DENSE_N_CUTOFF:
        return "dense"
    # "blocked" is exact with the same O(b·n) memory guarantee, so the default
    # never silently trades accuracy; the stochastic estimator is opt-in.
    return "blocked"


def _blocked_residual_fro2(Kop: SPSDOperator, approx: SPSDApprox,
                           block_size: Optional[int], mesh=None,
                           extra_plans=()):
    """(||K - CUC^T||_F², ||K||_F², extra results) in ONE panel sweep.

    ``extra_plans`` ride the same pass (e.g. the subspace-iteration K Ω of
    ``error_vs_best_rank_k``); their results come back in order.
    """
    C32 = approx.C.astype(jnp.float32)
    M = _mm(approx.U.astype(jnp.float32), C32.T)          # (c, n)
    *extras, (num, den) = Kop.sweep(
        [*extra_plans, sweep_lib.ResidualFroPlan(C32, M)],
        block_size=block_size, mesh=mesh)
    return num, den, extras


def _hutchinson_residual_fro2(Kop: SPSDOperator, approx: SPSDApprox,
                              probes: int, key: jax.Array,
                              block_size: Optional[int], mesh=None,
                              extra_plans=()):
    """Rademacher estimates of (||K - CUC^T||_F², ||K||_F²), plus the
    results of any ``extra_plans`` fused into the same probe sweep."""
    Z = jax.random.rademacher(key, (Kop.n, probes), dtype=jnp.float32)
    *extras, KZ = Kop.sweep([*extra_plans, sweep_lib.MatmulPlan(Z)],
                            block_size=block_size, mesh=mesh)
    KZ = KZ.astype(jnp.float32)
    RZ = KZ - approx.matmat(Z).astype(jnp.float32)
    return jnp.sum(RZ * RZ) / probes, jnp.sum(KZ * KZ) / probes, extras


def relative_error(K, approx: SPSDApprox, method: str = "auto",
                   block_size: Optional[int] = None, probes: int = 64,
                   key: Optional[jax.Array] = None, mesh=None) -> jnp.ndarray:
    """||K - C U C^T||_F² / ||K||_F²  (Fig. 3/4 y-axis).

    The streaming methods cost exactly ONE sweep over the kernel row panels
    (shardable via ``mesh``); together with the fused ``fast_model`` that
    bounds model + error at two evaluations of each kernel entry — or one,
    via ``fast_model_with_error``.
    """
    Kop = as_operator(K)
    method = _resolve_error_method(Kop, method)
    if method == "dense":
        Kd = Kop.full().astype(jnp.float32)  # repro: allow-dense(exact f32 oracle, auto-gated to n<=2048)
        R = Kd - approx.dense().astype(jnp.float32)  # repro: allow-dense(same oracle branch)
        return jnp.sum(R * R) / jnp.sum(Kd * Kd)
    if method == "blocked":
        num, den, _ = _blocked_residual_fro2(Kop, approx, block_size, mesh)
        return num / den
    if method == "hutchinson":
        key = default_probe_key() if key is None else key
        num, den, _ = _hutchinson_residual_fro2(Kop, approx, probes, key,
                                                block_size, mesh)
        return num / den
    raise ValueError(f"unknown error method {method!r}")


def _subspace_eigvals_from_Y(Kop: SPSDOperator, Y: jnp.ndarray, k: int,
                             power_iters: int,
                             block_size: Optional[int], mesh=None):
    """Finish subspace iteration given the first product Y = K Ω.

    The remaining cost is ``power_iters`` power passes plus the Rayleigh
    quotient — (1 + power_iters) sweeps.  Factored out so callers that
    already have a sweep in flight (``error_vs_best_rank_k``) can fold the
    Y = K Ω pass into it instead of paying a dedicated one.
    """
    for _ in range(power_iters):
        Q, _ = jnp.linalg.qr(Y)
        Y = Kop.matmat(Q, block_size=block_size, mesh=mesh)
    Q, _ = jnp.linalg.qr(Y)
    B = Q.T @ Kop.matmat(Q, block_size=block_size, mesh=mesh)
    B = 0.5 * (B + B.T)
    lam = jnp.linalg.eigvalsh(B)[::-1]
    return lam[:k]


def streaming_topk_eigvals(K, k: int, key: Optional[jax.Array] = None,
                           oversample: int = 8, power_iters: int = 2,
                           block_size: Optional[int] = None,
                           mesh=None) -> jnp.ndarray:
    """Top-k eigenvalues of an SPSD operator via randomized subspace iteration.

    Halko-Martinsson-Tropp: Y = K Ω, a few power passes, then the Rayleigh
    quotient Q^T K Q — every K application streams through ``matmat``, so the
    cost is (2 + power_iters) blocked passes and O(n·(k+p)) memory.
    """
    Kop = as_operator(K)
    key = default_probe_key() if key is None else key
    q = min(Kop.n, k + oversample)
    Y = Kop.matmat(jax.random.normal(key, (Kop.n, q), dtype=jnp.float32),
                   block_size=block_size, mesh=mesh)
    return _subspace_eigvals_from_Y(Kop, Y, k, power_iters, block_size, mesh)


def error_vs_best_rank_k(K, approx: SPSDApprox, k: int, method: str = "auto",
                         block_size: Optional[int] = None, probes: int = 64,
                         key: Optional[jax.Array] = None,
                         mesh=None) -> jnp.ndarray:
    """||K - CUC^T||_F² / ||K - K_k||_F²  (the 1+ε target of Thm 3/Remark 4).

    Streaming methods use ||K - K_k||_F² = ||K||_F² - Σ_{i≤k} λ_i² (K SPSD)
    with the top spectrum by randomized subspace iteration — whose FIRST
    product Y = K Ω rides the same panel sweep as the residual accumulation
    (blocked) or the Hutchinson probes, so the whole metric costs
    (2 + power_iters) sweeps instead of (3 + power_iters).
    """
    Kop = as_operator(K)
    method = _resolve_error_method(Kop, method)
    if method == "dense":
        Kd = Kop.full().astype(jnp.float32)  # repro: allow-dense(exact eigen-tail oracle, auto-gated to n<=2048)
        evals = jnp.linalg.eigvalsh(Kd)
        # A kernel of rank ≤ k has an exactly-zero tail; floor it the same
        # way the streaming branch does (1e-12·||K||_F²) so the ratio stays
        # finite instead of inf/nan.
        fro2 = jnp.sum(evals ** 2)
        tail = jnp.sum(jnp.sort(evals ** 2)[: Kd.shape[0] - k])
        tail = jnp.maximum(tail, 1e-12 * fro2)
        R = Kd - approx.dense().astype(jnp.float32)  # repro: allow-dense(same oracle branch)
        return jnp.sum(R * R) / tail
    key = default_probe_key() if key is None else key
    keig, kprobe = jax.random.split(key)
    n = Kop.n
    q = min(n, k + 8)                       # streaming_topk_eigvals defaults
    power_iters = 2
    omega_plan = sweep_lib.MatmulPlan(
        jax.random.normal(keig, (n, q), dtype=jnp.float32))
    if method == "blocked":
        num, fro2, (Y,) = _blocked_residual_fro2(
            Kop, approx, block_size, mesh, extra_plans=[omega_plan])
    elif method == "hutchinson":
        num, fro2, (Y,) = _hutchinson_residual_fro2(
            Kop, approx, probes, kprobe, block_size, mesh,
            extra_plans=[omega_plan])
    else:
        raise ValueError(f"unknown error method {method!r}")
    lam = _subspace_eigvals_from_Y(Kop, Y, k, power_iters, block_size, mesh)
    tail = jnp.maximum(fro2 - jnp.sum(lam ** 2), 1e-12 * fro2)
    return num / tail
