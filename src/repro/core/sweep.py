"""Single-sweep multi-product panel engine (the Table-3 "#Entries" workhorse).

The paper's linear-in-n claim hinges on how few kernel entries are ever
*evaluated*.  PR 1's streaming substrate evaluated each (b × n) row panel once
per product — once for C = K P, once per S^T K S, once per error estimator —
so on-the-fly kernels paid 4-6× the entry cost the model actually needs.

This module fixes that with *panel plans*: small accumulator objects that all
consume the same panel.  ``sweep_panels`` walks the row panels exactly once
under ``jax.lax.scan`` and feeds every plan from the single materialization,
so one sweep yields an arbitrary set of products (K @ S for each sketch,
column gathers for C, diag/trace/Frobenius accumulators, Hutchinson probes,
adaptive residual norms) for one evaluation of each kernel tile.

A plan implements three methods::

    init(nrows, ncols)            -> carry (f32 pytree of zeros)
    update(carry, panel, idx, valid) -> carry   # MUST mask by ``valid``
    finalize(carry)               -> result

All carries are pure sums of per-panel contributions (row-indexed outputs are
scatter-added into zero-initialized buffers), which makes the engine
data-parallel for free: with a ``Mesh`` carrying a ``data`` axis
(``distributed/sharding.py``), the panel starts are partitioned across
devices with ``shard_map`` and the per-device partial carries are reduced
with ``psum``.  On a trivial (single-device / absent) mesh the engine falls
back to the plain sequential scan — bit-identical results either way, up to
float reassociation across devices.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# Row panels are capped at roughly this many f32 elements (b·ncols), so the
# streaming paths stay ~128 MB regardless of problem size.
PANEL_ELEMENT_BUDGET = 1 << 25

# Plans contract f32 panels at full f32 precision: a TPU's default f32 matmul
# is one bf16 pass, which would put the panel route ~1e-3 off the fused one.
_HIGHEST = jax.lax.Precision.HIGHEST


def panel_block_size(ncols: int, block_size: Optional[int]) -> int:
    if block_size is not None:
        return max(1, int(block_size))
    return max(128, min(4096, PANEL_ELEMENT_BUDGET // max(ncols, 1)))


def resolved_block_size(nrows: int, ncols: int, block_size: Optional[int],
                        data_parallel: int = 1) -> int:
    """The panel height a sweep actually uses.

    The budgeted (or requested) size, clamped to ``nrows`` so short operators
    pay no clamp padding.  With ``data_parallel`` > 1 the size is shrunk so
    the panel count is (as nearly as possible) a multiple of the device
    count — sentinel padding panels would each evaluate a full b×ncols block
    of throwaway kernel entries, so balancing by *resizing* keeps the sharded
    sweep's evaluated-entry count within one thin panel of the sequential
    sweep's.
    """
    bs = min(panel_block_size(ncols, block_size), max(nrows, 1))
    if data_parallel > 1:
        nblocks = -(-nrows // bs)
        target = data_parallel * (-(-nblocks // data_parallel))
        bs = -(-nrows // target)
    return bs


def num_panels(nrows: int, ncols: int, block_size: Optional[int],
               data_parallel: int = 1) -> int:
    """How many panels one sweep over ``nrows`` rows touches."""
    return -(-nrows // resolved_block_size(nrows, ncols, block_size,
                                           data_parallel))


def local_slab_rows(nrows: int, ncols: int, block_size: Optional[int],
                    data_parallel: int = 1) -> int:
    """Rows of the per-device slab a sharded sweep covers (panels · b).

    This is the height a ``slab_fn`` claim is invoked with on each shard —
    the contiguous local row range, including the ≤ one thin panel of clamp /
    sentinel padding the panel route would also evaluate.
    """
    bs = resolved_block_size(nrows, ncols, block_size, data_parallel)
    nblocks = -(-nrows // bs)
    if data_parallel > 1:
        nblocks += (-nblocks) % data_parallel
    return (nblocks // data_parallel) * bs


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MatmulPlan:
    """A @ V for V (ncols × m): the streaming matmat as a plan."""

    V: jnp.ndarray

    def tree_flatten(self):
        return (self.V,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((nrows, self.V.shape[1]), jnp.float32)

    def update(self, carry, panel, idx, valid):
        y = jnp.matmul(panel.astype(jnp.float32), self.V.astype(jnp.float32),
                       precision=_HIGHEST)
        return carry.at[idx].add(y * valid.astype(jnp.float32)[:, None])

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnGatherPlan:
    """A[:, col_idx] — the C = K P gather, free once the panel exists (the
    fused route computes it in its launch from the selected points)."""

    col_idx: jnp.ndarray

    def tree_flatten(self):
        return (self.col_idx,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((nrows, self.col_idx.shape[0]), jnp.float32)

    def update(self, carry, panel, idx, valid):
        y = jnp.take(panel, self.col_idx, axis=1).astype(jnp.float32)
        return carry.at[idx].add(y * valid.astype(jnp.float32)[:, None])

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SketchRightPlan:
    """A S for a sketch object exposing ``S.right`` (SRHT / CountSketch)."""

    S: object
    s: int

    def tree_flatten(self):
        return (self.S,), (self.s,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((nrows, self.s), jnp.float32)

    def update(self, carry, panel, idx, valid):
        y = self.S.right(panel.astype(jnp.float32))
        return carry.at[idx].add(y * valid.astype(jnp.float32)[:, None])

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FrobeniusPlan:
    """||A||_F² accumulated panel-by-panel."""

    def tree_flatten(self):
        return (), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls()

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((), jnp.float32)

    def update(self, carry, panel, idx, valid):
        p32 = panel.astype(jnp.float32)
        return carry + jnp.sum(p32 * p32 * valid.astype(jnp.float32)[:, None])

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DiagPlan:
    """diag(A) (square operators): one gather per panel row."""

    def tree_flatten(self):
        return (), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls()

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((nrows,), jnp.float32)

    def update(self, carry, panel, idx, valid):
        d = jnp.take_along_axis(panel, idx[:, None], axis=1)[:, 0]
        return carry.at[idx].add(d.astype(jnp.float32)
                                 * valid.astype(jnp.float32))

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ResidualFroPlan:
    """(||K - C M||_F², ||K||_F²) for a low-rank C M (M = U C^T) in one pass.

    ``C``: (nrows, c) f32, ``M``: (c, ncols) f32.
    """

    C: jnp.ndarray
    M: jnp.ndarray

    def tree_flatten(self):
        return (self.C, self.M), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def init(self, nrows: int, ncols: int):
        return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

    def update(self, carry, panel, idx, valid):
        p32 = panel.astype(jnp.float32)
        resid = p32 - jnp.matmul(jnp.take(self.C, idx, axis=0), self.M,
                                 precision=_HIGHEST)
        v = valid.astype(jnp.float32)[:, None]
        return (carry[0] + jnp.sum(resid * resid * v),
                carry[1] + jnp.sum(p32 * p32 * v))

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ProjResidualColNormPlan:
    """Adaptive-sampling residual column norms ||(I − Q Qᵀ) K||² in ONE pass.

    With Q an orthonormal basis of range(C) (zero-σ columns masked to 0),
    ||(I − QQᵀ) K e_j||² = ||K e_j||² − ||Qᵀ K e_j||², so one sweep
    accumulating per-column norms of K alongside the (q × ncols) product
    Qᵀ K replaces PR 1's matmat pass + residual pass per adaptive round.

    ``mask`` (optional, (nrows,)) row-masks the statistics so padded
    (ragged-batch) operators never leak padding rows into the norms.
    """

    Q: jnp.ndarray           # (nrows, q) f32, orthonormal (masked) columns
    mask: Optional[jnp.ndarray] = None   # (nrows,) 1.0 valid / 0.0 padding

    def tree_flatten(self):
        return (self.Q, self.mask), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def init(self, nrows: int, ncols: int):
        return (jnp.zeros((ncols,), jnp.float32),
                jnp.zeros((self.Q.shape[1], ncols), jnp.float32))

    def update(self, carry, panel, idx, valid):
        colnorms, QtK = carry
        rowm = valid.astype(jnp.float32)
        if self.mask is not None:
            rowm = rowm * jnp.take(self.mask.astype(jnp.float32), idx)
        p32 = panel.astype(jnp.float32) * rowm[:, None]
        colnorms = colnorms + jnp.sum(p32 * p32, axis=0)
        QtK = QtK + jnp.matmul(jnp.take(self.Q, idx, axis=0).T, p32,
                               precision=_HIGHEST)
        return (colnorms, QtK)

    def finalize(self, carry):
        colnorms, QtK = carry
        return jnp.maximum(colnorms - jnp.sum(QtK * QtK, axis=0), 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GramPlan:
    """Σ panelᵀ panel — the blocked Gram pass (R Rᵀ over column panels)."""

    dim: int

    def tree_flatten(self):
        return (), (self.dim,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0])

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((self.dim, self.dim), jnp.float32)

    def update(self, carry, panel, idx, valid):
        p32 = panel.astype(jnp.float32) * valid.astype(jnp.float32)[:, None]
        return carry + jnp.matmul(p32.T, p32, precision=_HIGHEST)

    def finalize(self, carry):
        return carry


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RowQuadFormPlan:
    """q_i = panel_i W panel_iᵀ per row — blocked leverage-score scoring."""

    W: jnp.ndarray           # (ncols, ncols) f32 (small: r × r)

    def tree_flatten(self):
        return (self.W,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def init(self, nrows: int, ncols: int):
        return jnp.zeros((nrows,), jnp.float32)

    def update(self, carry, panel, idx, valid):
        p32 = panel.astype(jnp.float32)
        q = jnp.sum(jnp.matmul(p32, self.W, precision=_HIGHEST) * p32, axis=1)
        return carry.at[idx].add(q * valid.astype(jnp.float32))

    def finalize(self, carry):
        return carry


# ---------------------------------------------------------------------------
# route selection (the operator capability protocol)
# ---------------------------------------------------------------------------
#
# PR 3 hardwired the fused-Pallas routing decision inside ``RBFKernel.sweep``;
# it now lives here, behind two small capability hooks any operator may
# implement:
#
#     supports_fused_matmat() -> bool
#         True when the operator can answer a whole matmul-shaped plan bundle
#         with one fused launch (e.g. a Pallas-backed ``PairwiseKernel``).
#     fused_rows(row_idx, Vs, col_idx=None) -> tuple[jnp.ndarray, ...]
#         [A[row_idx, :] @ V for V in Vs] for a contiguous row slab
#         (``row_idx=None`` means all rows — the square single-device case),
#         preceded by the column gather A[row_idx, col_idx] when ``col_idx``
#         is given.
#
# Every sweep consumer (``fast_model``, ``fast_cur``, eig/error metrics,
# adaptive sampling) goes through ``sweep_operator`` and therefore gets the
# fast path for every capable operator with zero per-call-site changes.  The
# chosen route is recorded on ``op._last_sweep_route`` ('pallas_fused' |
# 'pallas_fused_sharded' | 'panel') for instrumentation
# (``CountingOperator.last_route``).

def is_matmul_shaped(plans: Sequence) -> bool:
    """True when every plan is answered by one fused launch: A @ V for a
    dense right-hand side (matmats), or A[:, idx] (column gathers, computed
    in the launch from the selected points, not as products)."""
    plans = list(plans)
    return bool(plans) and all(
        isinstance(p, (MatmulPlan, ColumnGatherPlan)) for p in plans)


def fused_right_hand_sides(plans: Sequence):
    """A matmul-shaped bundle's operands for one fused launch: the dense f32
    right-hand sides of its matmats, in plan order, and the column indices
    of its gathers, concatenated in plan order (None without a gather).
    The launch computes the gathered columns from the selected points, so
    they cost n·c kernel entries and no right-hand side."""
    Vs = tuple(p.V.astype(jnp.float32) for p in plans
               if isinstance(p, MatmulPlan))
    gathers = [p.col_idx for p in plans if isinstance(p, ColumnGatherPlan)]
    col_idx = jnp.concatenate(gathers) if gathers else None
    return Vs, col_idx


def _in_plan_order(plans: Sequence, outs):
    """A fused launch's outputs (the gathered columns first when there is a
    gather, then one product per matmat) back in plan order."""
    outs = list(outs)
    C = (outs.pop(0) if any(isinstance(p, ColumnGatherPlan) for p in plans)
         else None)
    products = iter(outs)
    res, off = [], 0
    for p in plans:
        if isinstance(p, ColumnGatherPlan):
            c = p.col_idx.shape[0]
            res.append(C[:, off:off + c])
            off += c
        else:
            res.append(next(products))
    return res


def sweep_operator(op, plans: Sequence, block_size: Optional[int] = None,
                   mesh: Optional[Mesh] = None):
    """Run a plan bundle over a square operator's rows, fastest route first.

    Matmul-shaped bundles on a capable operator collapse into ONE fused
    multi-RHS launch per device: a single square launch on a trivial mesh
    ('pallas_fused'), or — on a non-trivial mesh — a per-shard claim through
    the engine's ``slab_fn`` hook, where each device runs one rectangular
    row-slab launch and the partial carries are psum-reduced exactly like the
    panel route ('pallas_fused_sharded').  Column gathers ride the same
    launch, computed from the selected points (a bundle of gathers alone on
    a trivial mesh is one ``op.columns`` call).  Everything else walks the
    blocked panel scan over ``op.block`` ('panel').  Each route runs inside a
    ``span`` named ``sweep.<route>``.
    """
    from repro.core.instrument import span   # instrument imports this module

    plans = list(plans)
    n = op.n
    fused = op.supports_fused_matmat() and is_matmul_shaped(plans)
    # the precision policy rides the route string as a suffix ('pallas_fused'
    # stays 'pallas_fused' under the default f32 policy, so route assertions
    # and startswith-based metering are unchanged)
    prec = getattr(op, "precision", "f32")
    suffix = "" if prec == "f32" else "+" + prec
    op._last_slab_mode = None          # only sharded fused claims set this
    if fused:
        Vs, col_idx = fused_right_hand_sides(plans)
    if fused and mesh_data_size(mesh) <= 1:
        op._last_sweep_route = "pallas_fused" + suffix
        with span("sweep.pallas_fused"):
            outs = ((op.columns(col_idx),) if not Vs
                    else op.fused_rows(None, Vs, col_idx))
            return _in_plan_order(plans, outs)
    if fused:
        op._last_sweep_route = "pallas_fused_sharded" + suffix
        use_slab = op.supports_prefetch_slab()
        op._last_slab_mode = "prefetch" if use_slab else "gather"

        def slab_fn(row_idx, valid):
            # One rectangular launch for this shard's row slab: only the
            # slab's kernel tiles are evaluated, each exactly once.  The
            # scalar-prefetch claim addresses the slab inside the launch
            # (row_idx[0] is the slab start — clamped starts only occur on
            # all-sentinel shards, whose contributions ``valid`` zeroes);
            # the gather claim materializes the row slice.
            if use_slab:
                outs = op.fused_slab(row_idx[0], row_idx.shape[0], Vs,
                                     col_idx)
            else:
                outs = op.fused_rows(row_idx, Vs, col_idx)
            v = valid.astype(jnp.float32)[:, None]
            return tuple(
                p.init(n, n).at[row_idx].add(o * v) for p, o in
                zip(plans, _in_plan_order(plans, outs)))

        # panel_fn=None: the claim is unconditional, the scan never runs
        with span("sweep.pallas_fused_sharded"):
            return sweep_panels(None, n, n, plans, block_size=block_size,
                                mesh=mesh, slab_fn=slab_fn)
    op._last_sweep_route = "panel"
    cols = jnp.arange(n)
    with span("sweep.panel"):
        return sweep_panels(lambda idx: op.block(idx, cols), n, n, plans,
                            block_size=block_size, mesh=mesh)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def _mesh_data_axes(mesh: Optional[Mesh]):
    """The ('pod','data') subset present in ``mesh`` — lazy import so this
    module stays importable without the distributed package."""
    if mesh is None:
        return ()
    from repro.distributed.sharding import data_axes
    return data_axes(mesh)


def mesh_data_size(mesh: Optional[Mesh]) -> int:
    """Total data-parallel width of ``mesh`` (1 for None / trivial meshes)."""
    axes = _mesh_data_axes(mesh)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def sweep_panels(panel_fn, nrows: int, ncols: int, plans: Sequence,
                 block_size: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 slab_fn=None):
    """Apply every plan to each (b × ncols) row panel in a single pass.

    ``panel_fn(idx)`` materializes rows ``idx`` (a (b,) int array; tail panels
    are clamped to the last row and masked via ``valid``).  Returns
    ``[plan.finalize(carry) for plan in plans]``.  ``panel_fn`` may be None
    when ``slab_fn`` is provided (an unconditional claim — the panel scan is
    then unreachable).

    With a non-trivial ``mesh`` the panel starts are partitioned over the
    mesh's data axes via ``shard_map``; each device scans its local panels and
    the additive carries are ``psum``-reduced, so results match the
    single-device sweep to float-reassociation accuracy.

    ``slab_fn`` is the per-shard fast-path hook: an operator that can produce
    a whole contiguous row slab's worth of carries in one shot (e.g. the
    fused multi-RHS Pallas launch of ``PairwiseKernel``) claims the plan
    bundle by
    passing ``slab_fn(row_idx, valid) -> tuple(carry per plan)``.  ``row_idx``
    is the shard's full local row range — ``local_slab_rows`` rows, clamped
    into ``[0, nrows)`` with ``valid`` masking clamp/sentinel padding — and
    the returned carries must equal what the panel scan would have produced
    (row-indexed outputs scatter-added into ``plan.init`` zeros, masked by
    ``valid``).  The psum reduction and finalize step are shared with the
    panel route, so a claim changes the schedule, never the contract.
    """
    plans = list(plans)
    dp = mesh_data_size(mesh)
    bs = resolved_block_size(nrows, ncols, block_size, dp)
    nblocks = -(-nrows // bs)

    def local_sweep(starts):
        def body(carry, start):
            idx = start + jnp.arange(bs)
            valid = idx < nrows
            idx = jnp.clip(idx, 0, nrows - 1)
            panel = panel_fn(idx)
            carry = tuple(p.update(c, panel, idx, valid)
                          for p, c in zip(plans, carry))
            return carry, None
        init = tuple(p.init(nrows, ncols) for p in plans)
        carry, _ = jax.lax.scan(body, init, starts)
        return carry

    def local_carry(starts_local, npanels_local):
        if slab_fn is None:
            return local_sweep(starts_local)
        # starts are contiguous ascending multiples of bs (sentinels == nrows
        # sort last), so the shard's panels tile exactly the row range
        # [starts_local[0], starts_local[0] + npanels_local·bs) ∩ [0, nrows).
        idx = starts_local[0] + jnp.arange(npanels_local * bs)
        valid = idx < nrows
        idx = jnp.clip(idx, 0, nrows - 1)
        return tuple(slab_fn(idx, valid))

    starts = jnp.arange(nblocks) * bs
    if dp > 1:
        axes = _mesh_data_axes(mesh)
        # resolved_block_size already rebalanced the panel count to (near) a
        # multiple of dp; any remainder is padded with sentinel starts == n
        # (``valid`` all-False -> exact zero contributions, ≤ dp-1 thin
        # panels of waste).
        pad = (-nblocks) % dp
        if pad:
            starts = jnp.concatenate(
                [starts, jnp.full((pad,), nrows, starts.dtype)])
        per_dev = starts.shape[0] // dp

        def sharded(starts_local):
            carry = local_carry(starts_local, per_dev)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, axes), carry)

        # check_vma=False: the Pallas launch inside a slab claim carries no
        # varying-manual-axes annotation for the checker to follow
        carry = jax.shard_map(sharded, mesh=mesh,
                              in_specs=P(axes), out_specs=P(),
                              check_vma=False)(starts)
    else:
        carry = local_carry(starts, nblocks)
    return [p.finalize(c) for p, c in zip(plans, carry)]
