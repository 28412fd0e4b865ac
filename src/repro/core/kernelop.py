"""Implicit SPSD operators with a streaming blockwise access protocol.

The paper's efficiency story depends on *never* materializing the n×n kernel
matrix (Fig. 1, Table 3 "#Entries" column).  ``SPSDOperator`` exposes exactly
the access patterns the fast model needs:

- ``columns(idx)``   -> K[:, idx]           (n × c)    for C = K P
- ``block(ri, ci)``  -> K[ri][:, ci]        (|ri|×|ci|) for S^T K S
- ``diag()``                                            for trace tricks
- ``full()``         -> K                   (small-n tests only)

plus the *streaming* protocol every large-n code path is built on:

- ``sweep(plans)``        -> the single-pass multi-product panel engine
  (``repro.core.sweep``): every plan consumes each (b × n) row panel from ONE
  materialization, and a non-trivial ``mesh`` partitions the panels over the
  data axis with ``shard_map`` (psum-reduced partial products).
- ``map_row_panels(fn)``  -> fn applied to (b × n) row panels, ``jax.lax.map``
  over row blocks; peak memory O(b·n), never O(n²).
- ``matmat(V)``           -> K @ V streamed through row panels.
- ``frobenius_norm_sq()`` -> ||K||_F² accumulated panel-by-panel.

Route selection lives in the sweep engine (``sweep.sweep_operator``) behind a
small capability protocol — ``supports_fused_matmat()`` / ``fused_rows()`` —
so any capable operator gets the fused Pallas fast paths at every call site.

``PairwiseKernel`` computes entries on the fly from the d-dimensional data
for ANY registered ``KernelSpec`` (rbf, laplacian, matern32, polynomial,
linear, or user-registered — see ``repro.kernels.pairwise.specs``); with
``use_pallas=True`` blocks and matmul-shaped sweeps run the fused pairwise
Pallas template, whose kernel tiles never leave VMEM.  ``RBFKernel`` and
``LinearKernel`` survive as thin back-compat constructors over it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import sweep as sweep_lib
from repro.kernels.pairwise import specs as pairwise_specs
from repro.kernels.pairwise.specs import KernelSpec

# Back-compat aliases; the canonical definitions live in repro.core.sweep.
_PANEL_ELEMENT_BUDGET = sweep_lib.PANEL_ELEMENT_BUDGET
_panel_block_size = sweep_lib.panel_block_size


class SPSDOperator:
    n: int

    # -- pointwise access ---------------------------------------------------

    def block(self, row_idx: jnp.ndarray, col_idx: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def columns(self, idx: jnp.ndarray) -> jnp.ndarray:
        """K[:, idx] through a ``ColumnGatherPlan`` sweep over the selected
        columns.

        The default deliberately does NOT call ``block(arange(n), idx)``:
        that would eagerly build an n-length row index (and for most
        implementations gather a full copy of the backing data) on every
        gather.  Instead the panel engine walks row panels of the n × c
        *selected-column view* ``block(rows, idx)`` — row indices only ever
        exist per-panel inside the scan, peak memory is O(b·c), and exactly
        the n·c requested entries are evaluated (the entry count
        ``CountingOperator`` meters for a gather).  Implementations with a
        cheaper direct form (dense K, factored or pairwise kernels)
        override this.
        """
        idx = jnp.asarray(idx)
        c = idx.shape[0]
        (C,) = sweep_lib.sweep_panels(
            lambda rows: self.block(rows, idx), self.n, c,
            [sweep_lib.ColumnGatherPlan(jnp.arange(c))])
        return C

    def full(self) -> jnp.ndarray:
        raise NotImplementedError

    def diag(self) -> jnp.ndarray:
        raise NotImplementedError

    # -- fused-sweep capability protocol (see sweep.sweep_operator) ---------

    @property
    def precision(self) -> str:
        """Tile-evaluation precision policy of this operator's launches
        (``'f32'`` unless the backing spec says otherwise) — recorded on
        ``_last_sweep_route`` by the sweep engine."""
        return "f32"

    def supports_fused_matmat(self) -> bool:
        """True when ``fused_rows`` answers matmul-shaped plan bundles."""
        return False

    def fused_rows(self, row_idx: Optional[jnp.ndarray], Vs,
                   col_idx: Optional[jnp.ndarray] = None):
        """[K[row_idx, :] @ V for V in Vs] in one fused launch (row_idx=None
        -> all rows), preceded by the column gather K[row_idx, col_idx]
        when ``col_idx`` is given.  Only called when
        ``supports_fused_matmat()``."""
        raise NotImplementedError

    def supports_prefetch_slab(self) -> bool:
        """True when ``fused_slab`` can answer a contiguous row slab with a
        scalar-prefetch launch (no gathered row copy)."""
        return False

    def fused_slab(self, start_row, slab_len: int, Vs,
                   col_idx: Optional[jnp.ndarray] = None):
        """[K[start:start+slab_len, :] @ V for V in Vs] with the slab
        addressed inside the launch (``start_row`` may be traced), preceded
        by the slab's column gather when ``col_idx`` is given.  Rows at
        indices ≥ n are clamp duplicates the caller must mask.  Only called
        when ``supports_prefetch_slab()``."""
        raise NotImplementedError

    def cross(self, Xq: jnp.ndarray, Vs):
        """[K(Xq, ·) @ V for V in Vs] for OUT-OF-SAMPLE query points Xq.

        The query-time primitive of the serving path (``repro.serve``): one
        rectangular launch between new points and this operator's data,
        contracted against every right-hand side.  Only data-backed operators
        (``PairwiseKernel``) can extend the kernel to unseen points; index-
        backed operators (``DenseSPSD``) have no notion of a query point.
        """
        raise NotImplementedError(
            f"{type(self).__name__} is not data-backed; out-of-sample "
            f"queries need a PairwiseKernel (or another operator that can "
            f"evaluate K(x_query, x_data) from raw points)")

    # -- streaming protocol -------------------------------------------------

    def sweep(self, plans: Sequence, block_size: Optional[int] = None,
              mesh=None):
        """Run the multi-product panel engine over this operator's rows.

        Each kernel row panel is materialized exactly once and fed to every
        plan (``repro.core.sweep``), so a whole bundle of products — K @ S,
        column gathers for C, Hutchinson probes, residual norms — costs one
        evaluation of each kernel tile.  A non-trivial ``mesh`` shards the
        panels over its data axes via ``shard_map`` (single-device meshes and
        ``mesh=None`` fall back to the sequential scan).  Route selection —
        fused Pallas launches for matmul-shaped bundles on capable
        operators, the blocked panel scan otherwise — happens in
        ``sweep.sweep_operator`` and is recorded on ``_last_sweep_route``.
        """
        return sweep_lib.sweep_operator(self, plans, block_size=block_size,
                                        mesh=mesh)

    def map_row_panels(self, fn, block_size: Optional[int] = None):
        """Apply ``fn(panel, row_idx, valid)`` to consecutive (b × n) row panels.

        ``panel`` is K[row_idx, :] (tail panels are padded by clamping to the
        last row; ``valid`` masks the padding).  Results are stacked along a
        leading block axis — reductions sum over it, matmats reshape it away.
        Runs under ``jax.lax.map`` so only one panel is live at a time.
        """
        n = self.n
        bs = sweep_lib.resolved_block_size(n, n, block_size)
        nblocks = -(-n // bs)
        starts = jnp.arange(nblocks) * bs
        cols = jnp.arange(n)

        def body(start):
            idx = start + jnp.arange(bs)
            valid = idx < n
            idx = jnp.clip(idx, 0, n - 1)
            return fn(self.block(idx, cols), idx, valid)

        return jax.lax.map(body, starts)

    def matmat(self, V: jnp.ndarray, block_size: Optional[int] = None,
               mesh=None) -> jnp.ndarray:
        """K @ V without materializing K (footnote-2 memory trick)."""
        V2 = V if V.ndim == 2 else V[:, None]
        (out,) = self.sweep([sweep_lib.MatmulPlan(V2)],
                            block_size=block_size, mesh=mesh)
        return out if V.ndim == 2 else out[:, 0]

    def frobenius_norm_sq(self, block_size: Optional[int] = None,
                          mesh=None) -> jnp.ndarray:
        """||K||_F² accumulated over row panels (never forms K)."""
        (out,) = self.sweep([sweep_lib.FrobeniusPlan()],
                            block_size=block_size, mesh=mesh)
        return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseSPSD(SPSDOperator):
    K: jnp.ndarray

    def tree_flatten(self):
        return (self.K,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    @property
    def n(self) -> int:
        return int(self.K.shape[0])

    def columns(self, idx):
        return jnp.take(self.K, idx, axis=1)

    def block(self, row_idx, col_idx):
        return jnp.take(jnp.take(self.K, row_idx, axis=0), col_idx, axis=1)

    def full(self):
        return self.K

    def diag(self):
        return jnp.diagonal(self.K)

    def matmat(self, V, block_size: Optional[int] = None, mesh=None):
        return self.K @ V

    def frobenius_norm_sq(self, block_size: Optional[int] = None, mesh=None):
        K32 = self.K.astype(jnp.float32)
        return jnp.sum(K32 * K32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PairwiseKernel(SPSDOperator):
    """K_ij = entry_fn(stat(x_i, x_j)) for ANY registered ``KernelSpec``.

    One operator class for the whole kernel family: the spec supplies the
    pairwise statistic + elementwise entry function
    (``repro.kernels.pairwise.specs``), and this class supplies the operator
    protocol around it — on-the-fly blocks, the O(n·d) ``diag()`` shortcut,
    the direct n×c column gather, and the fused-sweep capability hooks
    (``supports_fused_matmat`` / ``fused_rows``) the sweep engine routes
    through, so every kernel rides the same single-launch multi-RHS Pallas
    sweeps and shard_map row-slab claims that PR 2/3 built for RBF::

        from repro.kernels.pairwise import specs
        K = PairwiseKernel(X, specs.get_spec("laplacian", gamma=0.5),
                           use_pallas=True)
        ap = spsd.fast_model(K, key, c=100, s=400, s_sketch="gaussian")
    """

    X: jnp.ndarray
    spec: KernelSpec
    use_pallas: bool = False

    def tree_flatten(self):
        return (self.X,), (self.spec, self.use_pallas)

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = object.__new__(cls)          # skip subclass back-compat inits
        obj.X, obj.spec, obj.use_pallas = children[0], aux[0], aux[1]
        return obj

    @property
    def n(self) -> int:
        return int(self.X.shape[0])

    @property
    def precision(self) -> str:
        return self.spec.precision

    def with_precision(self, precision: str) -> "PairwiseKernel":
        """This operator under another tile-precision policy (same data,
        same routing; the spec variant is cached so jit keys stay stable)."""
        return PairwiseKernel(self.X, self.spec.with_precision(precision),
                              self.use_pallas)

    def l1_edges(self) -> Optional[jnp.ndarray]:
        """Sign-split segment table for the MXU l1dist route, or None.

        Built lazily (one host-side pass over X) and cached on the instance.
        None — the VPU reference route — for non-l1dist statistics, traced
        X (unflattened inside jit; such instances are ephemeral, nothing is
        cached), and data whose per-feature cardinality exceeds the segment
        budget (``signsplit.MAX_SEGMENTS``).
        """
        if self.spec.stat != "l1dist":
            return None
        if not hasattr(self, "_l1_edges_cache"):
            from repro.kernels.pairwise import signsplit
            plan = signsplit.build_plan(self.X)
            edges = None if plan is None else plan.edges
            if isinstance(self.X, jax.core.Tracer):
                return edges
            self._l1_edges_cache = edges
        return self._l1_edges_cache

    def l1_route(self, Xq=None) -> Optional[str]:
        """Which l1dist route this operator's launches take
        ('mxu_signsplit' | 'vpu_loop'; None for non-l1dist statistics) —
        surfaced in bench metadata so perf regressions are attributable.

        With ``Xq`` given, reports the QUERY-side routing decision for a
        ``cross(Xq, ...)`` launch: 'mxu_signsplit' only when a plan exists
        AND every query value lies on the plan's lattice
        (``signsplit.query_in_plan`` — the exactness contract for
        out-of-sample points), 'vpu_loop' otherwise.  After a ``cross``
        call the decision actually taken is recorded on
        ``_last_cross_l1_route``."""
        if self.spec.stat != "l1dist":
            return None
        if self.l1_edges() is None:
            return "vpu_loop"
        if Xq is None:
            return "mxu_signsplit"
        from repro.kernels.pairwise import signsplit
        return ("mxu_signsplit" if signsplit.query_in_plan(self.X, Xq)
                else "vpu_loop")

    def block(self, row_idx, col_idx):
        Xr = jnp.take(self.X, row_idx, axis=0)
        Xc = jnp.take(self.X, col_idx, axis=0)
        if self.use_pallas:
            from repro.kernels.pairwise import ops as pw_ops
            return pw_ops.kernel_block(self.spec, Xr, Xc,
                                       edges=self.l1_edges())
        return pairwise_specs.apply(self.spec, Xr, Xc, self.l1_edges())

    def columns(self, idx):
        # n·c entries straight from the data: no n-length row index, no row
        # gather — the columns ARE a (all-rows × selected-points) block.
        Xc = jnp.take(self.X, idx, axis=0)
        if self.use_pallas:
            from repro.kernels.pairwise import ops as pw_ops
            return pw_ops.kernel_block(self.spec, self.X, Xc,
                                       edges=self.l1_edges())
        return pairwise_specs.apply(self.spec, self.X, Xc, self.l1_edges())

    def full(self):
        return pairwise_specs.apply(self.spec, self.X, self.X,
                                    self.l1_edges())

    def diag(self):
        # O(n·d), touches no off-diagonal entry (constant for distance
        # statistics, row norms through entry_fn for the dot statistic).
        return pairwise_specs.diag(self.spec, self.X)

    def stat_operator(self) -> "PairwiseKernel":
        """Operator over the RAW pairwise statistic (identity entry
        function) — what per-spec bandwidth calibration quantiles stream
        from (``repro.kernels.pairwise.calibrate``).  Shares this operator's
        data, Pallas routing, and sweep machinery."""
        return PairwiseKernel(self.X, pairwise_specs.stat_only(self.spec),
                              self.use_pallas)

    # -- fused-sweep capability (sweep.sweep_operator routes through these) --

    def supports_fused_matmat(self) -> bool:
        return bool(self.use_pallas)

    def _landmarks(self, col_idx):
        return None if col_idx is None else jnp.take(self.X, col_idx, axis=0)

    def fused_rows(self, row_idx, Vs, col_idx=None):
        """One rectangular multi-RHS Pallas launch for a contiguous row slab:
        the slab's kernel tiles are computed once in VMEM and contracted
        against every right-hand side (``row_idx=None`` -> the square
        all-rows launch).  With ``col_idx`` the launch first returns the
        slab's columns K[row_idx, col_idx], computed from the landmark
        points X[col_idx] as ``columns`` computes them."""
        from repro.kernels.pairwise import ops as pw_ops
        Xr = self.X if row_idx is None else jnp.take(self.X, row_idx, axis=0)
        return pw_ops.kernel_matmat_multi_rows(
            self.spec, Xr, self.X, Vs, edges=self.l1_edges(),
            Xl=self._landmarks(col_idx))

    def supports_prefetch_slab(self) -> bool:
        return bool(self.use_pallas)

    def fused_slab(self, start_row, slab_len, Vs, col_idx=None):
        """The scalar-prefetch slab launch: the shard's contiguous row range
        is addressed inside the kernel via a prefetched row-block offset
        (``ops.kernel_matmat_multi_slab``), so no per-device row-slice copy
        of X is ever gathered; ``col_idx`` as in ``fused_rows``."""
        from repro.kernels.pairwise import ops as pw_ops
        return pw_ops.kernel_matmat_multi_slab(
            self.spec, self.X, start_row, int(slab_len), Vs,
            edges=self.l1_edges(), Xl=self._landmarks(col_idx))

    def cross(self, Xq, Vs):
        """[K(Xq, X) @ V for V in Vs] — the serving-path query launch.

        Exactly the ``fused_rows`` row-slab template with the slab rows
        replaced by the query points: the (n_q × n) rectangular kernel block
        is computed tile-by-tile in VMEM (``use_pallas``) and contracted
        against every head matrix in ONE launch, so a whole heterogeneous
        query bucket (KRR predictions + KPCA projections + feature maps)
        costs one evaluation of each cross-kernel entry.  The route — and
        the precision policy, as a ``+bf16_f32acc`` suffix — is recorded on
        ``_last_sweep_route`` like every sweep (``pallas_fused_rows`` /
        ``dense_rows``).

        The sign-split l1 route IS used for on-lattice queries: the plan's
        exactness contract covers out-of-sample points whose values all lie
        on this operator's own per-feature value lattice
        (``signsplit.query_in_plan`` — appended rows from the training
        pipeline are the common case), in which case the launch takes the
        MXU form (``+mxu_signsplit`` route suffix); off-lattice queries
        keep the VPU reference loop.  The decision is recorded on
        ``_last_cross_l1_route`` and queryable up front via
        ``l1_route(Xq)``.
        """
        from repro.kernels.pairwise import ops as pw_ops
        edges = None
        self._last_cross_l1_route = None
        if self.spec.stat == "l1dist":
            q_route = self.l1_route(Xq)
            self._last_cross_l1_route = q_route
            if q_route == "mxu_signsplit":
                edges = self.l1_edges()
        route = "pallas_fused_rows" if self.use_pallas else "dense_rows"
        if edges is not None:
            route += "+mxu_signsplit"
        if self.precision != "f32":
            route += "+" + self.precision
        self._last_sweep_route = route
        return pw_ops.kernel_matmat_multi_rows(
            self.spec, jnp.asarray(Xq), self.X, tuple(Vs),
            use_pallas=self.use_pallas, edges=edges)


@jax.tree_util.register_pytree_node_class
class RBFKernel(PairwiseKernel):
    """K_ij = exp(-|x_i - x_j|^2 / (2 sigma^2)) computed from X (n × d).

    Thin back-compat constructor over ``PairwiseKernel`` with the registry's
    ``rbf`` spec; all routing/streaming behavior lives in the base class.
    """

    def __init__(self, X: jnp.ndarray, sigma: float,
                 use_pallas: bool = False):
        PairwiseKernel.__init__(self, X, pairwise_specs.rbf(sigma),
                                use_pallas)

    @property
    def sigma(self) -> float:
        return self.spec.param("sigma")


@jax.tree_util.register_pytree_node_class
class LinearKernel(PairwiseKernel):
    """K = X X^T (n × n) from X (n × d).

    The ``linear`` spec through ``PairwiseKernel``, plus the factored
    O(n·d)-per-product fast paths the explicit X Xᵀ structure allows (a
    fused entry-wise sweep could never beat (Xᵀ V) first).
    """

    def __init__(self, X: jnp.ndarray, use_pallas: bool = False):
        PairwiseKernel.__init__(self, X, pairwise_specs.linear(), use_pallas)

    def columns(self, idx):
        return self.X @ jnp.take(self.X, idx, axis=0).T

    def matmat(self, V, block_size: Optional[int] = None, mesh=None):
        return self.X @ (self.X.T @ V)

    def frobenius_norm_sq(self, block_size: Optional[int] = None, mesh=None):
        # ||X X^T||_F² = ||X^T X||_F² — a d×d Gram, O(nd²) and O(d²) memory.
        G = self.X.astype(jnp.float32)
        G = G.T @ G
        return jnp.sum(G * G)


def as_operator(K) -> SPSDOperator:
    if isinstance(K, SPSDOperator):
        return K
    return DenseSPSD(jnp.asarray(K))
