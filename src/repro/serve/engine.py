"""serve_kernel_model: batched query answering over a KernelModelArtifact.

The whole query-time cost model is ONE rectangular cross-kernel launch per
bucket.  A bucket's requests — arbitrary mixes of KRR / KPCA / feature-map
tasks and query counts — are padded to the bucket height (``bucket_by_size``
bounds each request's padding at ``waste``), stacked into one flat
(rows × d) query block, and answered by a single
``op.cross(X_flat, heads)`` call: the fused row-slab Pallas template
computes each K(x_query, x_landmark) tile once in VMEM and contracts it
against every head the bucket needs.  Per-request outputs are slices of the
launch result; padding rows are computed-and-dropped (bounded by ``waste``),
never observed.

``op`` defaults to ``artifact.landmark_operator()`` and may be any wrapper
with the same ``cross`` contract — the smoke tests pass a
``CountingOperator`` and assert exactly one ``cross_sweeps`` tick per
bucket.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.spsd import bucket_by_size
from repro.serve.artifact import TASKS, KernelModelArtifact


@dataclasses.dataclass
class QueryRequest:
    """One inference request: ``task`` ∈ {'krr','kpca','features'} over query
    points ``X`` (n_q × d, same feature space as the training data)."""

    X: jnp.ndarray
    task: str = "krr"

    def __post_init__(self):
        if self.task not in TASKS:
            raise ValueError(f"unknown task {self.task!r}; one of {TASKS}")
        self.X = jnp.asarray(self.X, jnp.float32)
        if self.X.ndim == 1:
            self.X = self.X[None, :]

    @property
    def n_q(self) -> int:
        return int(self.X.shape[0])


@dataclasses.dataclass
class QueryResult:
    """``out`` is (n_q × t) predictions / (n_q × k) projections /
    (n_q × r) features depending on the request's task."""

    out: jnp.ndarray
    task: str
    bucket: int                       # which launch answered it (diagnostics)


def _as_request(q) -> QueryRequest:
    return q if isinstance(q, QueryRequest) else QueryRequest(X=q)


def answer_batch(artifact: KernelModelArtifact,
                 requests: Sequence[QueryRequest],
                 op=None, bucket: int = 0,
                 precision: Optional[str] = None) -> List[QueryResult]:
    """Answer one (already-bucketed) batch with ONE cross-kernel launch.

    Requests are padded to the batch's max height with zero points (their
    kernel rows are computed and discarded — the ``bucket_by_size`` waste
    bound), stacked, and every head any request needs rides the same launch
    as an extra right-hand side.  ``precision`` (when ``op`` is not given)
    overrides the artifact spec's tile policy for the cross launch.
    """
    requests = [_as_request(q) for q in requests]
    if not requests:
        return []
    if op is None:
        op = artifact.landmark_operator(precision=precision)
    tasks = tuple(t for t in TASKS
                  if any(r.task == t for r in requests))
    heads = tuple(artifact.heads[t].astype(jnp.float32) for t in tasks)

    h = max(r.n_q for r in requests)
    flat = jnp.concatenate(
        [jnp.pad(r.X, ((0, h - r.n_q), (0, 0))) for r in requests], axis=0)
    outs = op.cross(flat, heads)
    by_task: Dict[str, jnp.ndarray] = dict(zip(tasks, outs))

    results = []
    for i, r in enumerate(requests):
        block = by_task[r.task][i * h: i * h + r.n_q]
        results.append(QueryResult(out=block, task=r.task, bucket=bucket))
    return results


def plan_buckets(requests: Sequence[QueryRequest],
                 waste: float = 0.25) -> List[List[int]]:
    """Index groups per launch: ``bucket_by_size`` over the query counts, so
    each request pays at most a ``waste`` fraction of padding rows."""
    return bucket_by_size([r.n_q for r in requests], waste=waste)


def serve_kernel_model(
    artifact: KernelModelArtifact,
    queries,
    waste: float = 0.25,
    op=None,
    precision: Optional[str] = None,
) -> List[QueryResult]:
    """Answer a heterogeneous batch of queries: one rectangular fused launch
    per size bucket, results in input order.

    ``queries`` is a list of ``QueryRequest`` (or raw (n_q × d) arrays,
    treated as KRR requests).  ``precision`` (when ``op`` is not given)
    overrides the artifact spec's tile policy for every cross launch — the
    bf16_f32acc serving mode.  This is the one-shot entry point; the
    continuous-batching server (``repro.launch.serve_kernel``) calls
    ``plan_buckets`` + ``answer_batch`` itself so it can meter per-request
    latency.
    """
    requests = [_as_request(q) for q in queries]
    results: List[Optional[QueryResult]] = [None] * len(requests)
    if op is None:
        op = artifact.landmark_operator(precision=precision)
    for b, bucket in enumerate(plan_buckets(requests, waste)):
        answers = answer_batch(artifact, [requests[i] for i in bucket],
                               op=op, bucket=b)
        for i, res in zip(bucket, answers):
            results[i] = res
    return results


# ---------------------------------------------------------------------------
# dense oracles (parity targets for tests / the serve-smoke trace)
# ---------------------------------------------------------------------------

def dense_oracle(artifact: KernelModelArtifact, Xq: jnp.ndarray,
                 task: str = "krr") -> jnp.ndarray:
    """The non-Pallas reference: G = K(Xq, X_S) via the dense spec apply,
    head applied in plain jnp at full f32 precision.  KRR additionally has
    the independent ``krr_reference`` below (no Woodbury, no artifact
    head)."""
    from repro.kernels.pairwise import specs as pw_specs
    G = pw_specs.apply(artifact.spec, jnp.asarray(Xq, jnp.float32),
                       artifact.X_landmarks)
    return jnp.matmul(G, artifact.heads[task].astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def krr_reference(artifact: KernelModelArtifact, Xq: jnp.ndarray,
                  y: jnp.ndarray) -> jnp.ndarray:
    """KRR on the approximated kernel in float64, at any n: neither the n×n
    Ĉ = C U Cᵀ nor the server's Woodbury identity.

    A thin QR C = Q R and the eigendecomposition R U Rᵀ = V Λ Vᵀ give
    Ĉ = (QV) Λ (QV)ᵀ, so w = (Ĉ + αI)⁻¹ y has Qᵀ w = V (Λ + αI)⁻¹ Vᵀ Qᵀ y,
    and the prediction k̂(x,·) w = K(x,X_S) U Cᵀ w = K(x,X_S) U Rᵀ Qᵀ w.
    Q is never formed: Qᵀ y = R⁻ᵀ Cᵀ y.  O(n·c²) time and O(n·c) memory —
    the serving parity reference at deployment size (``dense_krr_oracle``
    is its small-n cross-check).  Pass every query of a trace at once: the
    factorization is paid once per call."""
    import numpy as np

    from repro.kernels.pairwise import specs as pw_specs
    C = np.asarray(artifact.C, np.float64)
    U = np.asarray(artifact.U, np.float64)
    R = np.linalg.qr(C, mode="r")
    lam, V = np.linalg.eigh(R @ U @ R.T)
    y2 = np.asarray(y[:, None] if y.ndim == 1 else y, np.float64)
    Qty = np.linalg.solve(R.T, C.T @ y2)
    Qtw = V @ ((V.T @ Qty) / (lam + artifact.alpha)[:, None])
    G = np.asarray(
        pw_specs.apply(artifact.spec, jnp.asarray(Xq, jnp.float32),
                       artifact.X_landmarks), np.float64)
    return jnp.asarray(G @ (U @ (R.T @ Qtw)), jnp.float32)


def dense_krr_oracle(artifact: KernelModelArtifact, Xq: jnp.ndarray,
                     y: jnp.ndarray) -> jnp.ndarray:
    """End-to-end dense KRR on the approximated kernel: solve
    (C U Cᵀ + αI) w = y with a direct dense solve (no Woodbury identity),
    then extend with k̂(x,·) = K(x,X_S) U Cᵀ.  Forms the n×n Ĉ, so it is a
    small-n test oracle only; ``krr_reference`` is the scalable equivalent.
    The solve runs in f64 numpy (like the build-time Woodbury workspace) so
    the parity gate measures the serving path, not solver conditioning."""
    import numpy as np

    from repro.kernels.pairwise import specs as pw_specs
    C = np.asarray(artifact.C, np.float64)
    U = np.asarray(artifact.U, np.float64)
    n = C.shape[0]
    Khat = C @ U @ C.T
    y2 = np.asarray(y[:, None] if y.ndim == 1 else y, np.float64)
    w = np.linalg.solve(Khat + artifact.alpha * np.eye(n), y2)
    G = np.asarray(
        pw_specs.apply(artifact.spec, jnp.asarray(Xq, jnp.float32),
                       artifact.X_landmarks), np.float64)
    return jnp.asarray(G @ (U @ (C.T @ w)), jnp.float32)


def parity_gap(a: jnp.ndarray, b: jnp.ndarray) -> float:
    """max |a − b| / max(1, max|b|): the scale-normalized parity metric every
    serving assertion uses (≤1e-5 in the smoke gates)."""
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))
