"""MXU sign-split decomposition of the pairwise L1 statistic.

The laplacian kernel's ``l1dist`` statistic has no inner-product form, so the
tile path historically paid a d-iteration VPU ``fori_loop`` per (128 × 128)
kernel tile while every other registered statistic rode the MXU.  This module
gives ‖x−y‖₁ a matmul form via *sign-split segments*: partition each
feature's value range into segments (buckets) s with edges e₀ < e₁ < …; when
x_k and y_k fall in different segments the sign of (x_k − y_k) is determined
by the segment ORDER, so the signed contribution factorizes into products of
one-point functions — per-segment rank-d contractions the MXU can batch.

Derivation (per scalar u, v with segment indices i(u), i(v)):

    |u − v| = 1[i(u) > i(v)]·(u − v) + 1[i(v) > i(u)]·(v − u)
              + 1[i(u) = i(v)]·|u − v|

    1[i(u) > i(v)]·u = Σ_s (u·δ_s(u))·L_s(v)       δ_s(u) = 1[i(u) = s]
    1[i(u) > i(v)]·v = Σ_s δ_s(u)·(v·L_s(v))       L_s(v) = 1[i(v) < s]

so with per-point embeddings over (segment × feature) slots

    α(u) = ( ⊕_s u·δ_s(u),  ⊕_s −δ_s(u) )           (2·B·d dims)
    β(v) = ( ⊕_s L_s(v),    ⊕_s v·L_s(v) )          (2·B·d dims)

the cross-segment part of the distance is two MXU contractions:

    ‖x − y‖₁ = α(x)·β(y) + β(x)·α(y)   +   Σ_k 1[same segment]·|x_k − y_k|

The trailing same-segment residual vanishes — making the identity EXACT —
whenever every segment contains at most ONE distinct data value per feature.
``build_plan`` therefore derives the edges from the operator's own data
(midpoints between consecutive distinct values) and only returns a plan when
every feature's cardinality fits the segment budget; otherwise the caller
keeps the VPU reference loop.  Low-cardinality features are the common case
for the paper's laplacian workloads (the Gittens–Mahoney evaluation datasets
— letters, pendigits, mushrooms — are all small-integer or categorical), and
quantized/standardized pipelines hit it by construction.

Cost model per (R × C) tile: 2 contractions of inner dimension 2·d·B on the
MXU plus O((R + C)·d·B) VPU embedding work, versus the reference route's
d-step VPU loop over (R × C) tiles.  HBM traffic is unchanged — embeddings
are built in VMEM from the raw (tile × d) point tiles and the shared
(2, B·d) slot table; nothing of size n·d·B ever exists.  Every embedding is
a lane-dense 2-D (tile × B·d) array, so its VMEM footprint is what its
shape says; ``build_plan`` refuses plans wider than ``MAX_SLOTS``, which
keeps a tile body's embeddings inside the TPU's scoped VMEM.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: default per-feature segment budget: embeddings are 2·d·B wide, so 32
#: keeps the MXU contraction's inner dimension modest (512 at d=8) while
#: covering the small-integer / categorical cardinalities the laplacian
#: evaluation datasets actually have.
MAX_SEGMENTS = 32

#: widest plan (d·B slots per embedding half) the tile body may build.  The
#: embeddings are lane-dense (tile × d·B) arrays, so a tile's VMEM grows
#: with d·B; a v5e compile at 4,096 slots (d=128, B=32, both precisions)
#: passes.  Wider plans are refused and the data keeps the VPU route.
MAX_SLOTS = 4096


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SignSplitPlan:
    """Per-feature segment edges for the MXU l1dist route.

    ``edges`` is (d, B−1) f32, ascending per row, padded with +inf (padded
    segments are empty).  Exactness contract: every realized value of feature
    k — on BOTH sides of the pairwise block — lies in a segment of its own,
    which ``build_plan`` guarantees by placing edges at midpoints between
    consecutive distinct data values.
    """

    edges: jnp.ndarray

    def tree_flatten(self):
        return (self.edges,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    @property
    def segments(self) -> int:
        return int(self.edges.shape[1]) + 1


def build_plan(X, max_segments: int = MAX_SEGMENTS) -> Optional[SignSplitPlan]:
    """Derive sign-split edges from the data, or None when inapplicable.

    Host-side (numpy) one-time O(n·d log n) pass: per feature, the sorted
    distinct values; edges at consecutive midpoints.  Returns None — caller
    keeps the VPU reference route — when any feature has more than
    ``max_segments`` distinct values (continuous data), when the plan would
    need more than ``MAX_SLOTS`` embedding slots (d·B: the tile body would
    not fit the TPU's VMEM), or when ``X`` is a tracer (plans cannot be
    built under jit/vmap; the VPU route is always safe there).
    """
    if isinstance(X, jax.core.Tracer):
        return None
    Xh = np.asarray(X, np.float32)
    if Xh.ndim != 2 or not np.all(np.isfinite(Xh)):
        return None
    d = Xh.shape[1]
    per_feature = []
    for k in range(d):
        u = np.unique(Xh[:, k])
        if u.shape[0] > max_segments:
            return None
        per_feature.append((u[:-1] + u[1:]) / 2.0)
    width = max(max(len(m) for m in per_feature), 1)
    if d * (width + 1) > MAX_SLOTS:
        return None
    edges = np.full((d, width), np.inf, np.float32)
    for k, m in enumerate(per_feature):
        edges[k, :len(m)] = m
    return SignSplitPlan(edges=jnp.asarray(edges))


def query_in_plan(X, Xq) -> bool:
    """True iff every query value lies ON the plan data's lattice.

    The sign-split identity drops the same-segment residual, and
    ``build_plan`` places exactly one distinct data value of ``X`` in each
    segment — so the MXU form is exact for a query point iff each of its
    feature values EQUALS some realized value of that feature in ``X``
    (then a same-segment pairing implies equal values, residual 0).  This
    host-side membership check is what lets serving route ``cross`` through
    the MXU for on-lattice queries — e.g. appended rows drawn from the same
    categorical/quantized pipeline as the training data — while off-lattice
    queries keep the always-exact VPU loop.  Tracers (jit-abstract queries)
    and non-finite values are conservatively off-plan.
    """
    if isinstance(X, jax.core.Tracer) or isinstance(Xq, jax.core.Tracer):
        return False
    Xh = np.asarray(X, np.float32)
    Qh = np.asarray(Xq, np.float32)
    if Qh.ndim == 1:
        Qh = Qh[None, :]
    if Xh.ndim != 2 or Qh.ndim != 2 or Qh.shape[1] != Xh.shape[1]:
        return False
    if not np.all(np.isfinite(Qh)):
        return False
    return all(bool(np.isin(Qh[:, k], np.unique(Xh[:, k])).all())
               for k in range(Xh.shape[1]))


def slot_bounds(edges: jnp.ndarray) -> jnp.ndarray:
    """The (2, B·d) slot table a tile body embeds against, from (d, B−1)
    edges: row 0 holds each slot's lower edge e_{s−1}[k] (−inf for s = 0),
    row 1 its upper edge e_s[k] (+inf for the last segment), slot s·d + k.

    Built outside the Pallas kernels (a transpose + reshape of the small
    edge table), so the tile body only compares lane-dense rows."""
    d = edges.shape[0]
    edges = edges.astype(jnp.float32)
    ninf = jnp.full((d, 1), -jnp.inf, jnp.float32)
    pinf = jnp.full((d, 1), jnp.inf, jnp.float32)
    lo = jnp.concatenate([ninf, edges], axis=1)          # (d, B)
    hi = jnp.concatenate([edges, pinf], axis=1)
    return jnp.stack([lo.T.reshape(-1), hi.T.reshape(-1)])


def embed(X: jnp.ndarray, bounds: jnp.ndarray,
          compute_dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(α, β) sign-split embeddings, each (m, 2·B·d), from points (m, d)
    and a ``slot_bounds`` table.

    Pure jnp and shape-static, so it runs identically inside the Pallas tile
    body (point tiles in VMEM, slot table broadcast to every tile) and in
    the dense parity oracle.  Every intermediate is 2-D and lane-dense: X is
    tiled B times along lanes so slot s·d + k holds x_k.  Segment indicators
    are computed in f32 regardless of ``compute_dtype`` (they are exact 0/1
    decisions); the value-carrying slots are cast to ``compute_dtype`` so
    the bf16 tile policy quantizes exactly the same numbers the reference
    route quantizes.
    """
    d = X.shape[1]
    nseg = bounds.shape[1] // d
    xv = jnp.tile(X.astype(jnp.float32), (1, nseg))       # (m, B·d)
    above = (xv >= bounds[0:1, :]).astype(jnp.float32)   # x ≥ e_{s−1}
    below = (xv < bounds[1:2, :]).astype(jnp.float32)    # x < e_s
    delta = above * below                                # δ_s(x)
    L = 1.0 - above                                      # L_s(x)
    alpha = jnp.concatenate([xv * delta, -delta], axis=1)
    beta = jnp.concatenate([L, xv * L], axis=1)
    return alpha.astype(compute_dtype), beta.astype(compute_dtype)


def l1dist_slots(Xr: jnp.ndarray, Xc: jnp.ndarray, bounds: jnp.ndarray,
                 compute_dtype=jnp.float32) -> jnp.ndarray:
    """Pairwise ‖x−y‖₁ via the sign-split MXU form (two contractions) over
    a ``slot_bounds`` table.

    The SHARED implementation of the MXU route: ``kernel._entry_tile`` calls
    this on VMEM point tiles and the dense/oracle paths call it on whole
    blocks, so the Pallas and non-Pallas sign-split routes can never diverge.
    Accumulation is always f32 (``preferred_element_type``); only the
    operand tiles follow ``compute_dtype``, and f32 operands contract at
    ``Precision.HIGHEST``.
    """
    ar, br = embed(Xr, bounds, compute_dtype)
    ac, bc = embed(Xc, bounds, compute_dtype)
    dn = (((1,), (1,)), ((), ()))
    prec = (jax.lax.Precision.HIGHEST
            if jnp.dtype(compute_dtype) == jnp.float32 else None)
    out = jax.lax.dot_general(ar, bc, dimension_numbers=dn, precision=prec,
                              preferred_element_type=jnp.float32)
    out = out + jax.lax.dot_general(br, ac, dimension_numbers=dn,
                                    precision=prec,
                                    preferred_element_type=jnp.float32)
    return jnp.maximum(out, 0.0)


def l1dist(Xr: jnp.ndarray, Xc: jnp.ndarray, edges: jnp.ndarray,
           compute_dtype=jnp.float32) -> jnp.ndarray:
    """``l1dist_slots`` from a plan's (d, B−1) edge table."""
    return l1dist_slots(Xr, Xc, slot_bounds(edges), compute_dtype)
