"""One tiled Pallas sweep template for every pairwise kernel (TPU-native).

Generalization of the fused RBF kernels (paper Fig. 1 memory trick): each
kernel tile is produced from the point tiles — the pairwise *statistic* on
the MXU/VPU, then the spec's pure elementwise ``entry_fn`` on the VPU — and
is consumed while still in VMEM, so no kernel entry is ever staged in HBM:

- ``pairwise_block_padded``        one K block (the S^T K S / C panel path),
- ``pairwise_matmat_multi_padded`` [K(Xr, Xc) @ V for V in Vs] with each
  kernel tile computed ONCE and contracted against every right-hand side —
  the single-sweep panel engine at the kernel-tile level; given landmark
  points ``Xl`` it also returns the column gather C = K(Xr, Xl), computed
  from the landmark tiles in the first column steps of each row,
- ``pairwise_matmat_multi_slab``   the shard_map per-device fast path: the
  row slab is addressed INSIDE the launch via a scalar-prefetch row-offset
  index map (``PrefetchScalarGridSpec``), so each device's grid walks its
  contiguous block range of the shared padded X instead of contracting a
  gathered copy.

Statistics (``KernelSpec.stat``):

- ``'dot'``     xᵀy — one MXU contraction.
- ``'sqdist'``  ‖x−y‖₂² — MXU cross term + VPU norms/combine.
- ``'l1dist'``  ‖x−y‖₁ — with a sign-split segment table (``edges``, turned
  into a lane-dense slot table before the launch) two MXU contractions over
  per-point segment embeddings built in VMEM
  (``repro.kernels.pairwise.signsplit``); without one, the reference VPU
  ``fori_loop`` over the feature axis (live set independent of d).

Precision (``KernelSpec.precision``): point tiles and the kernel tile are
quantized to ``spec.tile_dtype()`` (bf16 under ``bf16_f32acc``); every MXU
contraction accumulates f32 via ``preferred_element_type``, and f32 operands
contract at ``Precision.HIGHEST``; ``entry_fn`` always sees an f32
statistic.  The dense fallback (``specs.stat_block``)
applies the identical policy, so routes stay comparable per mode.

Tiles are MXU/lane aligned.  The block and slab launches step 128 × 128;
the multi launch picks its (rows, columns) tile from its shapes
(``sweep_tile``: the largest on ``TILE_LADDER`` that divides its axes, takes
at most a quarter of each and fits ``VMEM_BUDGET``), so a large sweep pays
each grid step's fixed cost over more entries and streams the column points
and right-hand sides once per wider row tile.  HBM traffic stays
O((nr + nc)·d + Σ nc·m_i + Σ nr·m_i) per row tile — the Table-3 "#Entries"
story for the whole kernel family, not just RBF.

Every launch carries a stable ``name`` (``pairwise_block``,
``pairwise_matmat_multi``, ``pairwise_matmat_slab``) and a launch record in
its ``metadata`` (``launch_record``): the work it issues at the shapes it
launches with, counted by ``launch_work``.  Both land in the custom call's
HLO text, so a device trace finds each launch and its work by name.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pairwise import signsplit
from repro.kernels.pairwise.specs import KernelSpec, f32_precision, stat_block

BLOCK_R = 128
BLOCK_C = 128
#: the sides a fused sweep launch's grid tile may take, rows and columns
TILE_LADDER = (128, 256, 512, 1024)
#: the VMEM a fused sweep step may hold by ``sweep_vmem_bytes``
VMEM_BUDGET = 40 * 2 ** 20
#: the scoped VMEM a fused sweep launch asks the compiler for (a v5e core
#: has 128 MiB): the budget, with room for what the estimate leaves out
VMEM_LIMIT = 64 * 2 ** 20


def launch_work(spec: KernelSpec, nr: int, nc: int, d: int, m_total: int,
                l1_route: Optional[str] = None,
                segments: int = 0, landmarks: int = 0) -> Dict[str, int]:
    """The work ONE fused pairwise launch issues, split by unit.

    ``nr × nc`` kernel entries from (nr, d) × (nc, d) points, contracted
    against right-hand sides totalling ``m_total`` columns, plus
    ``nr × landmarks`` entries against (landmarks, d) landmark points that
    are written out as they are (the column gather C of a sweep launch).
    The split matters because the point of the MXU-everywhere pipeline is
    moving work from the ``vpu_flops`` bucket to the ``mxu_flops`` bucket:

    - ``dot``      2d MXU FLOPs/entry.
    - ``sqdist``   2d MXU FLOPs/entry + O(1) VPU combine (+ row norms).
    - ``l1dist``   route-dependent — 'mxu_signsplit' pays two contractions
      of inner dimension 2·d·B (B = ``segments``): 8·d·B MXU FLOPs/entry
      plus O((nr+nc)·d·B) VPU embedding; 'vpu_loop' pays ~4d VPU
      FLOPs/entry (subtract, abs, accumulate, loop bookkeeping).

    The V contraction adds 2·m_total MXU FLOPs per contracted entry;
    ``entry_fn`` is modeled at 8 VPU FLOPs/entry (transcendental-ish).  MXU
    FLOPs count one pass per contraction: the passes an f32 ``HIGHEST``
    contraction takes are not counted.  Bytes are the perfect-fusion HBM
    floor: points + right-hand sides in, outputs out — kernel tiles never
    touch HBM (that IS the fused template's claim).
    """
    contracted = nr * nc
    entries = nr * (nc + landmarks)
    points = nr + nc + landmarks
    stat = spec.stat
    if stat in ("dot", "sqdist"):
        width = d
        vpu = 4 * entries + 2 * points * d if stat == "sqdist" else 0
    elif stat == "l1dist":
        if l1_route == "mxu_signsplit":
            inner = 2 * d * max(int(segments), 1)
            width = 2 * inner                          # two contractions
            vpu = 6 * points * inner                   # VMEM embeddings
        else:
            width = 0
            vpu = 4 * d * entries                      # the reference loop
    else:  # pragma: no cover - specs validate stat
        raise ValueError(f"unknown stat {stat!r}")
    point_bytes = 2 if spec.precision != "f32" else 4
    return {"entries": entries,
            # stat + K-tile @ V
            "mxu_flops": 2 * entries * width + 2 * contracted * m_total,
            "vpu_flops": vpu + 8 * entries,                # + entry_fn
            "hbm_bytes": (points * d * point_bytes + (nc + nr) * m_total * 4
                          + nr * landmarks * 4)}


def launch_record(kernel: str, spec: KernelSpec, nr: int, nc: int, d: int,
                  ms, edges, landmarks: int = 0,
                  tile=(BLOCK_R, BLOCK_C)) -> Dict[str, str]:
    """The ``metadata`` of one pairwise launch: its kernel name, the MXU
    FLOPs and kernel entries it issues at its launch shapes (rows and
    columns padded to 128, each right-hand side to 128 columns, d as
    launched), under its tile precision, and its grid ``tile`` (rows x
    columns).  A launch that also computes a column gather from
    ``landmarks`` landmark points (padded to 128) says so in
    ``landmarks``; a launch without one has no such field.  Values are
    strings, as ``pallas_call`` requires."""
    route = "mxu_signsplit" if edges is not None else None
    segments = 0 if edges is None else int(edges.shape[1]) + 1
    work = launch_work(spec, nr, nc, d, sum(ms), route, segments, landmarks)
    rec = {"kernel": kernel, "mxu_flops": str(work["mxu_flops"]),
           "entries": str(work["entries"]), "precision": spec.precision,
           "passes": "not counted", "tile": f"{tile[0]}x{tile[1]}"}
    if landmarks:
        rec["landmarks"] = str(landmarks)
    return rec


def _entry_tile(xr_ref, xc_ref, spec: KernelSpec,
                b_ref=None) -> jnp.ndarray:
    """One f32 tile of kernel entries, (rows of ``xr_ref``, rows of
    ``xc_ref``), from two VMEM point tiles.  The statistic math is shared
    verbatim with the dense fallback (``specs.stat_block``: MXU
    contractions for dot/sqdist and the sign-split l1 route, the
    d-independent VPU ``fori_loop`` otherwise), so the Pallas and panel
    routes can never diverge.  Point tiles are quantized to the spec's
    precision policy; the statistic and ``entry_fn`` run in f32."""
    dt = spec.tile_dtype()
    xr = xr_ref[...].astype(dt)
    xc = xc_ref[...].astype(dt)
    bounds = b_ref[...] if b_ref is not None else None
    return spec.entry_fn(
        stat_block(spec.stat, xr, xc, spec.precision, bounds))


def _contract_tile(k_tile, v_ref, spec: KernelSpec) -> jnp.ndarray:
    """K-tile × V-tile under the precision policy: operands quantized to the
    tile dtype, f32 partial sums on the MXU (``Precision.HIGHEST`` for f32
    operands)."""
    dt = spec.tile_dtype()
    return jax.lax.dot_general(
        k_tile.astype(dt), v_ref[...].astype(dt),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=f32_precision(dt),
        preferred_element_type=jnp.float32,
    )


def _pairwise_block_kernel(xr_ref, xc_ref, *refs, spec: KernelSpec,
                           has_edges: bool):
    """One (BLOCK_R, BLOCK_C) output tile of kernel entries.

    xr_ref: (BLOCK_R, d) VMEM tile of row points
    xc_ref: (BLOCK_C, d) VMEM tile of column points
    refs:   optional (2, B·d) sign-split slot table, then the
            (BLOCK_R, BLOCK_C) VMEM output tile
    """
    b_ref = refs[0] if has_edges else None
    o_ref = refs[-1]
    o_ref[...] = _entry_tile(xr_ref, xc_ref, spec, b_ref)


def _pairwise_matmat_multi_kernel(xr_ref, xc_ref, *refs, spec: KernelSpec,
                                  nv: int, has_edges: bool, nl: int = 0):
    """Multi-right-hand-side fusion: one K tile, ``nv`` contractions.

    The (tr, tc) kernel tile is produced once and immediately contracted
    against every (tc, m_i) right-hand tile while still in VMEM.  ``refs``
    is an optional slot-table ref, an optional landmark tile ref (``nl`` >
    0), then ``nv`` V refs, then the outputs: C (with landmarks) and ``nv``
    accumulator refs; the column-tile grid axis j walks the contraction.
    With landmarks, the first ``nl`` column steps of each row also write
    the (tr, wl) chunk j of C = K(Xr, Xl) from the wl-row landmark tile j;
    C's output block stays at chunk nl − 1 after that, untouched.
    """
    b_ref = refs[0] if has_edges else None
    refs = refs[1:] if has_edges else refs
    xl_ref = refs[0] if nl else None
    refs = refs[1:] if nl else refs
    v_refs, o_refs = refs[:nv], refs[nv:]
    j = pl.program_id(1)
    if nl:
        c_ref, o_refs = o_refs[0], o_refs[1:]

        @pl.when(j < nl)
        def _():
            c_ref[...] = _entry_tile(xr_ref, xl_ref, spec, b_ref)

    if not nv:
        return

    @pl.when(j == 0)
    def _():
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros_like(o_ref)

    k_tile = _entry_tile(xr_ref, xc_ref, spec, b_ref)
    for v_ref, o_ref in zip(v_refs, o_refs):
        o_ref[...] += _contract_tile(k_tile, v_ref, spec)


def _bounds_in_spec(bounds, extra_grid_args: int = 0):
    """BlockSpec broadcasting the whole (2, B·d) slot table to every tile."""
    if extra_grid_args:
        return pl.BlockSpec(bounds.shape, lambda i, j, *_: (0, 0))
    return pl.BlockSpec(bounds.shape, lambda i, j: (0, 0))


def _landmark_width(landmarks: int, tc: int) -> int:
    """The rows of one landmark tile (and the columns of one chunk of C)
    under a ``tc``-column grid step: the widest that divides both, so C
    keeps its width padded to 128 and is never padded up to the tile."""
    return math.gcd(landmarks, tc)


def _landmark_blocks(Xl, nc: int, tc: int = BLOCK_C):
    """(tiles, rows per tile) of the landmark points a launch takes, (0, 0)
    without ``Xl``, checked: ``Xl`` rows come in whole 128-row blocks, and
    the column grid has a step for each tile."""
    if Xl is None:
        return 0, 0
    c = Xl.shape[0]
    assert c % BLOCK_C == 0 and 0 < c <= nc, Xl.shape
    wl = _landmark_width(c, tc)
    assert c // wl <= nc // tc, (c, nc, tc)
    return c // wl, wl


def _landmark_specs(nl: int, wl: int, tr: int, d: int):
    """The landmark tile's in-spec and C's out-spec: chunk min(j, nl − 1),
    so each is fetched or written once per grid row.  Index maps take any
    trailing scalar-prefetch refs."""
    chunk = lambda j: jnp.minimum(j, nl - 1)               # noqa: E731
    return (pl.BlockSpec((wl, d), lambda i, j, *_: (chunk(j), 0)),
            pl.BlockSpec((tr, wl), lambda i, j, *_: (i, chunk(j))))


def _lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane rows, as VMEM holds it."""
    return -(-width // 128) * 128


def sweep_vmem_bytes(tr: int, tc: int, d: int, inner: int, ms,
                     landmarks: int = 0) -> int:
    """The VMEM a fused sweep step holds at a (tr, tc) tile, estimated.

    ``d`` is the points' width, ``inner`` the statistic's inner width (d
    for ``sqdist``/``dot`` and the VPU l1 loop, the 2·B·d slots of a
    sign-split embedding), ``ms`` the right-hand sides' padded widths and
    ``landmarks`` the padded landmark rows.  Counted in f32 words, rows
    padded to 128 lanes: the double-buffered row, column and landmark point
    tiles; the working copies each tile's statistic makes at its inner
    width (squares for the norms, the three bf16 parts of an f32
    ``HIGHEST`` operand, sign-split embeddings), seven a row and two a
    column or landmark point, since the K tile and C's chunk each take
    their own of the row tile; the K tile and C's chunk with their
    statistic; the double-buffered V tiles and the split of one; the
    double-buffered accumulators and C chunk, and a step's product.  The
    weights bound what Mosaic allocates in v5e compiles of the rbf launch
    at d = 18 and d = 784 and of the sign-split one at ``MAX_SLOTS`` slots,
    at tiles from 128 × 128 to 2048 × 1024."""
    wl = _landmark_width(landmarks, tc) if landmarks else 0
    m = sum(ms)
    points = 2 * (tr + tc + wl) * _lanes(d)
    work = (7 * tr + 2 * (tc + wl)) * _lanes(inner)
    tiles = 2 * tr * (tc + wl)
    rhs = 2 * tc * m + tc * m * 3 // 2
    outs = 3 * tr * m + 2 * tr * wl
    return 4 * (points + work + tiles + rhs + outs)


def _fits_axis(t: int, n: int) -> bool:
    """128 always; a wider side divides its axis and is at most a quarter
    of it."""
    return t == BLOCK_R or (n % t == 0 and 4 * t <= n)


def sweep_tile(nr: int, nc: int, d: int, inner: int, ms,
               landmarks: int = 0) -> Tuple[int, int]:
    """The (rows, columns) grid tile of a fused sweep launch over padded
    (nr, d) × (nc, d) points, right-hand sides of padded widths ``ms`` and
    ``landmarks`` padded landmark rows (``inner`` as in
    ``sweep_vmem_bytes``).

    Each side is on ``TILE_LADDER``, divides its axis and is at most a
    quarter of it (128 always qualifies), the landmark tiles have a column
    step each, and the step's ``sweep_vmem_bytes`` fits ``VMEM_BUDGET``.
    Of those tiles the largest wins, the one with more rows on a tie: a
    grid step's fixed cost is spread over its entries, and every grid row
    streams all of the column points and right-hand sides once.
    """
    best = (BLOCK_R, BLOCK_C)
    for tr in TILE_LADDER:
        for tc in TILE_LADDER:
            if not (_fits_axis(tr, nr) and _fits_axis(tc, nc)):
                continue
            if landmarks and (landmarks // _landmark_width(landmarks, tc)
                              > nc // tc):
                continue
            if sweep_vmem_bytes(tr, tc, d, inner, ms,
                                landmarks) > VMEM_BUDGET:
                continue
            if (tr * tc, tr) > (best[0] * best[1], best[0]):
                best = (tr, tc)
    return best


def _inner_width(d: int, edges) -> int:
    """The statistic's inner width: 2·B·d slots on the sign-split route,
    d otherwise."""
    if edges is None:
        return d
    return 2 * d * (int(edges.shape[1]) + 1)


def pairwise_matmat_multi_padded(spec: KernelSpec, Xr: jnp.ndarray,
                                 Xc: jnp.ndarray, Vs,
                                 interpret: bool = False, edges=None,
                                 Xl=None):
    """[K(Xr, Xc) @ V for V in Vs] over padded inputs, one kernel launch.

    ``Xr`` and ``Xc`` may differ: the grid is rectangular (nr/tr × nc/tc
    for the tile ``sweep_tile`` picks from the launch's shapes), which is
    how a row *slab* of the kernel is evaluated against the full point set
    — each grid row computes only its slab's kernel tiles in VMEM and
    contracts them against every right-hand side exactly once.  Padded
    column points produce garbage kernel entries that meet zero-padded V
    rows, so their contribution vanishes for every ``entry_fn``.  ``edges``
    (optional) selects the sign-split MXU route for l1dist specs.

    ``Xl`` (optional): landmark points, rows padded to 128 and at most nc
    of them.  The launch then returns C = K(Xr, Xl) first, computed from
    the landmark tiles with the same statistic, ``entry_fn`` and precision
    policy as the K tiles, then the products.
    """
    nr, d = Xr.shape
    nc = Xc.shape[0]
    tile = sweep_tile(nr, nc, d, _inner_width(d, edges),
                      [V.shape[1] for V in Vs],
                      0 if Xl is None else Xl.shape[0])
    tr, tc = tile
    assert nr % tr == 0 and nc % tc == 0, (nr, nc, tile)
    for V in Vs:
        assert V.shape[0] == nc and V.shape[1] % 128 == 0, V.shape
    nl, wl = _landmark_blocks(Xl, nc, tc)
    grid = (nr // tr, nc // tc)
    has_edges = edges is not None
    in_specs = [
        pl.BlockSpec((tr, d), lambda i, j: (i, 0)),
        pl.BlockSpec((tc, d), lambda i, j: (j, 0)),
    ]
    operands = [Xr, Xc]
    if has_edges:
        bounds = signsplit.slot_bounds(edges)
        in_specs.append(_bounds_in_spec(bounds))
        operands.append(bounds)
    out_specs = [pl.BlockSpec((tr, V.shape[1]), lambda i, j: (i, 0))
                 for V in Vs]
    out_shape = [jax.ShapeDtypeStruct((nr, V.shape[1]), jnp.float32)
                 for V in Vs]
    if nl:
        xl_spec, c_spec = _landmark_specs(nl, wl, tr, d)
        in_specs.append(xl_spec)
        operands.append(Xl)
        out_specs.insert(0, c_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct((nr, nl * wl),
                                                 jnp.float32))
    in_specs += [
        pl.BlockSpec((tc, V.shape[1]), lambda i, j: (j, 0))
        for V in Vs
    ]
    return pl.pallas_call(
        functools.partial(_pairwise_matmat_multi_kernel, spec=spec,
                          nv=len(Vs), has_edges=has_edges, nl=nl),
        name="pairwise_matmat_multi",
        metadata=launch_record("pairwise_matmat_multi", spec, nr, nc, d,
                               [V.shape[1] for V in Vs], edges, nl * wl,
                               tile),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands, *Vs)


def _pairwise_matmat_slab_kernel(off_ref, xr_ref, xc_ref, *refs,
                                 spec: KernelSpec, nv: int, has_edges: bool,
                                 nl: int = 0):
    """Slab-launch body: identical math to the multi kernel; ``off_ref`` (the
    prefetched row-block offset) is consumed by the index maps, not here."""
    del off_ref
    _pairwise_matmat_multi_kernel(xr_ref, xc_ref, *refs, spec=spec, nv=nv,
                                  has_edges=has_edges, nl=nl)


def pairwise_matmat_multi_slab(spec: KernelSpec, X: jnp.ndarray,
                               off_blocks: jnp.ndarray, nblocks_r: int, Vs,
                               interpret: bool = False, edges=None, Xl=None):
    """[K(X[slab], X) @ V for V in Vs] with the slab addressed in-launch.

    The scalar-prefetch replacement for gather-then-launch: ``off_blocks``
    (a traced (1,) int32 — the slab's first 128-row block of the shared
    padded ``X``) rides ``PrefetchScalarGridSpec``, and the row point tile's
    index map adds it to the grid row index.  Each device of a shard_map
    sweep therefore walks its contiguous block range of the SAME operand
    ``X`` — no per-device row-slice copy of the point set is materialized,
    and one compiled launch serves every slab position.  Row-block indices
    are clamped to the last block so a tail slab reads (and the caller
    discards) duplicate rows instead of reading out of bounds.  ``Xl`` adds
    the slab's rows of C = K(X, Xl) as the first output, as in
    ``pairwise_matmat_multi_padded``.
    """
    n, d = X.shape
    assert n % BLOCK_R == 0, n
    max_block = n // BLOCK_R - 1
    nr = nblocks_r * BLOCK_R
    for V in Vs:
        assert V.shape[0] == n and V.shape[1] % 128 == 0, V.shape
    nl, wl = _landmark_blocks(Xl, n)

    def row_map(i, j, off_ref):
        return (jnp.minimum(off_ref[0] + i, max_block), 0)

    in_specs = [
        pl.BlockSpec((BLOCK_R, d), row_map),
        pl.BlockSpec((BLOCK_C, d), lambda i, j, off_ref: (j, 0)),
    ]
    operands = [X, X]
    has_edges = edges is not None
    if has_edges:
        bounds = signsplit.slot_bounds(edges)
        in_specs.append(_bounds_in_spec(bounds, extra_grid_args=1))
        operands.append(bounds)
    out_specs = [
        pl.BlockSpec((BLOCK_R, V.shape[1]), lambda i, j, off_ref: (i, 0))
        for V in Vs
    ]
    out_shape = [jax.ShapeDtypeStruct((nr, V.shape[1]), jnp.float32)
                 for V in Vs]
    if nl:
        xl_spec, c_spec = _landmark_specs(nl, wl, BLOCK_R, d)
        in_specs.append(xl_spec)
        operands.append(Xl)
        out_specs.insert(0, c_spec)
        out_shape.insert(0, jax.ShapeDtypeStruct((nr, nl * wl),
                                                 jnp.float32))
    in_specs += [
        pl.BlockSpec((BLOCK_C, V.shape[1]), lambda i, j, off_ref: (j, 0))
        for V in Vs
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks_r, n // BLOCK_C),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(_pairwise_matmat_slab_kernel, spec=spec,
                          nv=len(Vs), has_edges=has_edges, nl=nl),
        name="pairwise_matmat_slab",
        metadata=launch_record("pairwise_matmat_slab", spec, nr, n, d,
                               [V.shape[1] for V in Vs], edges, nl * wl),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(off_blocks, jnp.int32).reshape((1,)), *operands, *Vs)


def pairwise_block_padded(spec: KernelSpec, Xr: jnp.ndarray, Xc: jnp.ndarray,
                          interpret: bool = False,
                          edges=None) -> jnp.ndarray:
    """Pallas call over padded inputs; shapes must be multiples of the tiles."""
    nr, d = Xr.shape
    nc = Xc.shape[0]
    assert nr % BLOCK_R == 0 and nc % BLOCK_C == 0, (nr, nc)
    grid = (nr // BLOCK_R, nc // BLOCK_C)
    has_edges = edges is not None
    in_specs = [
        pl.BlockSpec((BLOCK_R, d), lambda i, j: (i, 0)),
        pl.BlockSpec((BLOCK_C, d), lambda i, j: (j, 0)),
    ]
    operands = [Xr, Xc]
    if has_edges:
        bounds = signsplit.slot_bounds(edges)
        in_specs.append(_bounds_in_spec(bounds))
        operands.append(bounds)
    return pl.pallas_call(
        functools.partial(_pairwise_block_kernel, spec=spec,
                          has_edges=has_edges),
        name="pairwise_block",
        metadata=launch_record("pairwise_block", spec, nr, nc, d, (), edges),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, nc), jnp.float32),
        interpret=interpret,
    )(*operands)
