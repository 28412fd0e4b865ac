"""Public jit'd wrappers for the pairwise kernel sweep template.

Handles arbitrary (non-tile-aligned) shapes by zero-padding the point sets and
slicing the output; padding rows produce garbage kernel values that are sliced
away (block path) or contracted against zero-padded V rows (matmat path),
never read.

Backend selection (interpret mode on CPU containers, compiled on real TPU) is
resolved at *call* time, not import time: each public wrapper reads
``jax.default_backend()`` when invoked — unless the caller passes an explicit
``interpret=`` — and threads the choice into the jit cache as a static
argument, so flipping the backend after import can never run a stale
interpret decision.  The ``spec`` is likewise a static argument: registry
factories cache their ``KernelSpec`` objects, so each (kernel, params) pair
costs one compilation, not one per call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.pairwise import kernel as _k
from repro.kernels.pairwise import specs as _specs
from repro.kernels.pairwise.specs import KernelSpec


def _interpret_mode() -> bool:
    """CPU containers interpret the TPU kernel; real TPU compiles it.

    A function (not a module constant) on purpose: the backend may be chosen
    after this module is imported, so the decision must be re-read per call.
    """
    return jax.default_backend() != "tpu"


def _pad_rows(X: jnp.ndarray, mult: int, at_least: int = 0) -> jnp.ndarray:
    n = X.shape[0]
    pad = max((-n) % mult, at_least - n)
    if pad == 0:
        return X
    return jnp.pad(X, ((0, pad), (0, 0)))


def _pad_cols(V: jnp.ndarray, mult: int) -> jnp.ndarray:
    m = V.shape[1]
    pad = (-m) % mult
    if pad == 0:
        return V
    return jnp.pad(V, ((0, 0), (0, pad)))


@partial(jax.jit, static_argnames=("spec", "use_pallas", "interpret"))
def _kernel_block_jit(Xr: jnp.ndarray, Xc: jnp.ndarray, edges,
                      spec: KernelSpec, use_pallas: bool,
                      interpret: bool) -> jnp.ndarray:
    if not use_pallas:
        return _specs.apply(spec, Xr, Xc, edges)
    nr, nc = Xr.shape[0], Xc.shape[0]
    Xrp = _pad_rows(Xr, _k.BLOCK_R)
    Xcp = _pad_rows(Xc, _k.BLOCK_C)
    out = _k.pairwise_block_padded(spec, Xrp, Xcp, interpret=interpret,
                                   edges=edges)
    return out[:nr, :nc]


def kernel_block(spec: KernelSpec, Xr: jnp.ndarray, Xc: jnp.ndarray,
                 use_pallas: bool = True, interpret: bool | None = None,
                 edges: jnp.ndarray | None = None) -> jnp.ndarray:
    """K-block entry_fn(stat(x_r, x_c)) of shape (len(Xr), len(Xc)).

    ``edges`` (a sign-split segment table, see
    ``repro.kernels.pairwise.signsplit``) opts l1dist statistics into the
    MXU route; ``None`` — and every non-l1dist stat — keeps the reference
    path.  ``None`` vs array is a pytree-structure change, so each choice
    costs one jit entry per spec, as before.
    """
    if interpret is None:
        interpret = _interpret_mode()
    return _kernel_block_jit(Xr, Xc, edges, spec, use_pallas, interpret)


def _landmark_rows(Xl):
    """Landmark points padded to whole tiles (None stays None), and their
    row count: the column grid must take at least that many rows."""
    if Xl is None:
        return None, 0
    Xlp = _pad_rows(Xl, _k.BLOCK_C)
    return Xlp, Xlp.shape[0]


def _dense_products(spec: KernelSpec, Xr, Xc, Vs, edges, Xl):
    """The fused launch's outputs by the dense evaluation: C = K(Xr, Xl)
    first when landmarks are given, then [K(Xr, Xc) @ V for V in Vs]."""
    K = _specs.apply(spec, Xr, Xc, edges)
    dt = spec.tile_dtype()
    outs = tuple(
        jax.lax.dot_general(K.astype(dt), V.astype(dt),
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            precision=_specs.f32_precision(dt),
                            preferred_element_type=jnp.float32)
        for V in Vs)
    if Xl is None:
        return outs
    return (_specs.apply(spec, Xr, Xl, edges),) + outs


def _widths(Vs, Xl):
    """The unpadded widths of a fused launch's outputs: C's (with
    landmarks) first, then each right-hand side's."""
    return ((Xl.shape[0],) if Xl is not None else ()) + tuple(
        V.shape[1] for V in Vs)


@partial(jax.jit, static_argnames=("spec", "use_pallas", "interpret"))
def _kernel_matmat_multi_rows_jit(Xr: jnp.ndarray, Xc: jnp.ndarray, Vs,
                                  edges, Xl, spec: KernelSpec,
                                  use_pallas: bool, interpret: bool):
    Vs = tuple(Vs)
    if not use_pallas:
        return _dense_products(spec, Xr, Xc, Vs, edges, Xl)
    Xlp, nl_rows = _landmark_rows(Xl)
    Xrp = _pad_rows(Xr, _k.BLOCK_R)
    Xcp = _pad_rows(Xc, _k.BLOCK_C, nl_rows)
    Vps = tuple(_pad_cols(_pad_rows(V, _k.BLOCK_C, nl_rows), 128)
                for V in Vs)
    outs = _k.pairwise_matmat_multi_padded(spec, Xrp, Xcp, Vps,
                                           interpret=interpret, edges=edges,
                                           Xl=Xlp)
    nr = Xr.shape[0]
    return tuple(out[:nr, :m] for out, m in zip(outs, _widths(Vs, Xl)))


def kernel_matmat_multi_rows(spec: KernelSpec, Xr: jnp.ndarray,
                             Xc: jnp.ndarray, Vs, use_pallas: bool = True,
                             interpret: bool | None = None,
                             edges: jnp.ndarray | None = None,
                             Xl: jnp.ndarray | None = None):
    """[K(Xr, Xc) @ V for V in Vs] — the rectangular row-slab fusion.

    The gather-based fast path of the sweep engine: the caller materializes
    its row slab ``Xr = X[r0:r1]`` and passes the full column points ``Xc``,
    so only that slab's kernel tiles are ever computed — once, in VMEM, at
    the tile ``kernel.sweep_tile`` picks — and contracted against every
    right-hand side.  Prefer
    ``kernel_matmat_multi_slab`` when the slab is a contiguous range of
    ``Xc`` — it addresses the slab in-launch instead of copying it.

    ``Xl`` (optional landmark points): the same launch also returns the
    column gather C = K(Xr, Xl), first, computed from the landmark tiles.
    """
    if interpret is None:
        interpret = _interpret_mode()
    return _kernel_matmat_multi_rows_jit(Xr, Xc, tuple(Vs), edges, Xl, spec,
                                         use_pallas, interpret)


@partial(jax.jit,
         static_argnames=("spec", "slab_len", "use_pallas", "interpret"))
def _kernel_matmat_multi_slab_jit(X: jnp.ndarray, start_row, Vs, edges, Xl,
                                  spec: KernelSpec, slab_len: int,
                                  use_pallas: bool, interpret: bool):
    Vs = tuple(Vs)
    n = X.shape[0]
    start = jnp.asarray(start_row, jnp.int32)
    if not use_pallas:
        # dense fallback mirrors the clip-gather semantics: rows past n read
        # the last row and are discarded by the caller's validity mask
        row_idx = jnp.clip(start + jnp.arange(slab_len), 0, n - 1)
        Xr = jnp.take(X, row_idx, axis=0)
        return _dense_products(spec, Xr, X, Vs, edges, Xl)
    Xlp, nl_rows = _landmark_rows(Xl)
    Xp = _pad_rows(X, _k.BLOCK_R, nl_rows)
    Vps = tuple(_pad_cols(_pad_rows(V, _k.BLOCK_C, nl_rows), 128)
                for V in Vs)
    # align the dynamic start down to a 128-row block boundary; the launch
    # covers [off·128, off·128 + nblocks·128) and the requested slab is cut
    # out afterwards (within ∈ [0, 128), so one extra block always suffices)
    off = start // _k.BLOCK_R
    within = start - off * _k.BLOCK_R
    nblocks = (slab_len + 2 * _k.BLOCK_R - 1) // _k.BLOCK_R
    outs = _k.pairwise_matmat_multi_slab(spec, Xp, off, nblocks, Vps,
                                         interpret=interpret, edges=edges,
                                         Xl=Xlp)
    return tuple(
        jax.lax.dynamic_slice_in_dim(out, within, slab_len, axis=0)[:, :m]
        for out, m in zip(outs, _widths(Vs, Xl)))


def kernel_matmat_multi_slab(spec: KernelSpec, X: jnp.ndarray, start_row,
                             slab_len: int, Vs, use_pallas: bool = True,
                             interpret: bool | None = None,
                             edges: jnp.ndarray | None = None,
                             Xl: jnp.ndarray | None = None):
    """[K(X[start:start+slab_len], X) @ V for V in Vs] without gathering.

    The scalar-prefetch slab launch: ``start_row`` may be a TRACED scalar —
    it rides a ``PrefetchScalarGridSpec`` into the row-tile index map, so
    one compiled launch serves every slab position of a shard_map sweep and
    no device ever materializes a row-slice copy of ``X``.  Rows at indices
    ≥ n (a tail slab) are duplicates of the last row/block; callers mask
    them (the sweep engine's validity mask already does).  ``Xl`` adds the
    slab's rows of C = K(X, Xl) as the first output.
    """
    if interpret is None:
        interpret = _interpret_mode()
    return _kernel_matmat_multi_slab_jit(X, start_row, tuple(Vs), edges, Xl,
                                         spec, int(slab_len), use_pallas,
                                         interpret)


def kernel_matmat_multi(spec: KernelSpec, X: jnp.ndarray, Vs,
                        use_pallas: bool = True,
                        interpret: bool | None = None,
                        edges: jnp.ndarray | None = None):
    """[K(X, X) @ V for V in Vs] with each kernel tile computed ONCE.

    All right-hand sides (projection sketches, Hutchinson probes) are
    contracted against the same VMEM-resident kernel tile in a single
    Pallas launch.  The square special case of
    ``kernel_matmat_multi_rows``.
    """
    return kernel_matmat_multi_rows(spec, X, X, Vs, use_pallas=use_pallas,
                                    interpret=interpret, edges=edges)


def kernel_matmat(spec: KernelSpec, X: jnp.ndarray, V: jnp.ndarray,
                  use_pallas: bool = True,
                  interpret: bool | None = None,
                  edges: jnp.ndarray | None = None) -> jnp.ndarray:
    """K(X, X) @ V fused: kernel tiles never leave VMEM (streaming matmat)."""
    squeeze = V.ndim == 1
    V2 = V[:, None] if squeeze else V
    (out,) = kernel_matmat_multi(spec, X, (V2,), use_pallas=use_pallas,
                                 interpret=interpret, edges=edges)
    return out[:, 0] if squeeze else out


@partial(jax.jit, static_argnames=("spec", "interpret"))
def _sketched_gram_jit(Xs: jnp.ndarray, scales, edges, spec: KernelSpec,
                       interpret):
    blk = _kernel_block_jit(Xs, Xs, edges, spec, True, interpret)
    if scales is not None:
        blk = blk * (scales[:, None] * scales[None, :])
    return blk


def sketched_gram(spec: KernelSpec, Xs: jnp.ndarray,
                  scales: jnp.ndarray | None = None,
                  interpret: bool | None = None,
                  edges: jnp.ndarray | None = None) -> jnp.ndarray:
    """S^T K S for a column sketch S given the selected points Xs = X[idx].

    ``edges`` (optional): a sign-split segment table covering ``Xs`` routes
    an l1dist statistic through the MXU form (selected points are a subset
    of the operator's data, so the operator's own table stays exact)."""
    if interpret is None:
        interpret = _interpret_mode()
    return _sketched_gram_jit(Xs, scales, edges, spec, interpret)
