"""Instrumented operator wrappers — the paper's Table-3 "#Entries" meter —
and ``span``, the one helper that names a phase of the program.

``span(name)`` (a context manager, or a decorator) enters
``jax.named_scope(name)``, which writes the name into the ``op_name`` of
every op traced inside it, and ``jax.profiler.TraceAnnotation(name)``, which
writes a host span on the profiler's clock whenever a trace is being taken.
It leaves the compiled program unchanged; with the profiler off it costs
one context enter per eager call.  The certified build's phases are
``spsd.select``, ``sweep.<route>``, ``spsd.sketch_block``, ``spsd.fast_u``
and ``spsd.certify``.

``CountingOperator`` wraps any ``SPSDOperator`` and records how many kernel
entries each pipeline actually *evaluates*, which is the quantity the
paper's efficiency claims are about.  Counters are plain Python ints bumped
at call/trace time (every public entry point in this repo invokes the
operator protocol from Python, so one ``sweep`` call == one pass over the
panels regardless of how ``jax.lax.scan`` re-executes the traced body):

- ``sweeps``  : panel-engine passes (each evaluates every row panel once)
- ``panels``  : total row panels materialized across those sweeps
- ``entries`` : kernel entries evaluated (sweeps count nblocks·b·n incl.
                clamp padding; direct block/columns/diag calls count their
                exact extent).  The fused Pallas routes evaluate the same
                row extent — per shard, one rectangular slab of
                ``local_slab_rows`` rows instead of a panel scan — so the
                count model holds for them unchanged.
- ``fused_sweeps`` : the subset of ``sweeps`` the inner operator claimed
                with a fused Pallas launch (single-device multi-RHS or the
                per-shard slab route); ``last_route`` records the most
                recent routing decision verbatim (including any
                ``+bf16_f32acc`` precision suffix)
- ``bf16_sweeps`` : the subset of sweeps/cross launches evaluated under a
                non-f32 tile-precision policy; ``last_precision`` records
                the policy of the most recent launch and ``last_slab_mode``
                whether a sharded claim used the scalar-prefetch slab
                launch ('prefetch') or the gathered row copy ('gather')
- ``append_sweeps`` : thin rectangular maintenance launches
                (``append_cross``) from the incremental append-row path
                (``repro.serve.incremental``) — metered separately from
                query-side ``cross_sweeps`` so the serving invariant
                (cross launches == query buckets) and the maintenance
                invariant (ONE thin sweep per appended batch, O(b·c)
                entries) are independently assertable
- ``blocks`` / ``columns`` / ``diags`` / ``fulls`` : direct-access calls

Used by the parity/entry-count tests (fast_model + streaming error must stay
≤ 2 sweeps; the fused ``fast_model_with_error`` at exactly 1) and by
``benchmarks/bench_time.py --streaming`` to print measured entry counts
alongside wall time.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax

from repro.core import sweep as sweep_lib
from repro.core.kernelop import SPSDOperator


@contextlib.contextmanager
def span(name: str):
    """Name a phase: a device scope (``op_name``) and a host profiler span."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


class CountingOperator(SPSDOperator):
    """Transparent counting proxy around an ``SPSDOperator``."""

    def __init__(self, inner: SPSDOperator):
        self.inner = inner
        self.reset()

    def reset(self):
        self.counts = {"sweeps": 0, "panels": 0, "entries": 0,
                       "fused_sweeps": 0, "cross_sweeps": 0,
                       "append_sweeps": 0, "bf16_sweeps": 0,
                       "blocks": 0, "columns": 0, "diags": 0, "fulls": 0}
        self.last_route = None
        self.last_precision = None
        self.last_slab_mode = None
        self._in_sweep = False

    @property
    def n(self) -> int:
        return self.inner.n

    def rebind(self, inner: SPSDOperator) -> "CountingOperator":
        """Swap the wrapped operator WITHOUT resetting the meters.

        The incremental-maintenance path grows an operator's corpus between
        rounds (appended rows); long-lived wrappers — a serving replica's
        counter, the budget-regression harness — rebind to the grown
        operator so cumulative counts stay comparable across the growth,
        while every per-call count (``_count_sweep`` panels/entries,
        ``cross``'s n_q·n) reads ``self.n`` at call time and therefore
        tracks the live corpus automatically."""
        self.inner = inner
        return self

    # -- direct access (counted exactly) ------------------------------------

    def block(self, row_idx, col_idx):
        if not self._in_sweep:
            self.counts["blocks"] += 1
            self.counts["entries"] += int(row_idx.shape[0]) * int(col_idx.shape[0])
        return self.inner.block(row_idx, col_idx)

    def columns(self, idx):
        self.counts["columns"] += 1
        self.counts["entries"] += self.n * int(idx.shape[0])
        return self.inner.columns(idx)

    def diag(self):
        self.counts["diags"] += 1
        self.counts["entries"] += self.n
        return self.inner.diag()

    def full(self):
        self.counts["fulls"] += 1
        self.counts["entries"] += self.n * self.n
        return self.inner.full()  # repro: allow-dense(counting passthrough — the meter itself)

    # -- streaming protocol (counted per pass) ------------------------------

    def _count_sweep(self, block_size, mesh=None):
        dp = sweep_lib.mesh_data_size(mesh)
        bs = sweep_lib.resolved_block_size(self.n, self.n, block_size, dp)
        nblocks = -(-self.n // bs)
        if dp > 1:
            nblocks += (-nblocks) % dp       # sentinel padding panels
        self.counts["sweeps"] += 1
        self.counts["panels"] += nblocks
        self.counts["entries"] += nblocks * bs * self.n

    def sweep(self, plans: Sequence, block_size: Optional[int] = None,
              mesh=None):
        self._count_sweep(block_size, mesh)
        self._in_sweep = True
        try:
            # delegate to the inner op so its fast paths (e.g. the fused
            # Pallas multi-RHS launch) stay engaged under instrumentation
            out = self.inner.sweep(plans, block_size=block_size, mesh=mesh)
        finally:
            self._in_sweep = False
        # attribute the route only on success, so a sweep that raised before
        # dispatching can never inherit the previous call's routing decision
        self._attribute(getattr(self.inner, "_last_sweep_route", "panel"))
        return out

    def _attribute(self, route: str):
        self.last_route = route
        self.last_precision = getattr(self.inner, "precision", "f32")
        self.last_slab_mode = getattr(self.inner, "_last_slab_mode", None)
        if route.startswith("pallas_fused"):
            self.counts["fused_sweeps"] += 1
        if self.last_precision != "f32":
            self.counts["bf16_sweeps"] += 1

    def cross(self, Xq, Vs):
        """Query-side rectangular launches (``repro.serve``): one
        ``cross_sweeps`` tick and exactly n_q · n evaluated entries per call
        — the serving acceptance tests assert one tick per query bucket."""
        self.counts["cross_sweeps"] += 1
        self.counts["entries"] += int(Xq.shape[0]) * self.n
        out = self.inner.cross(Xq, Vs)
        self._attribute(getattr(self.inner, "_last_sweep_route",
                                "dense_rows"))
        return out

    def append_cross(self, Xq, Vs):
        """The incremental append-row maintenance launch: same rectangular
        shape as ``cross`` but metered as ``append_sweeps`` (not
        ``cross_sweeps``), so the O(b·c) absorb claim — ONE thin sweep of
        exactly n_new · n entries per appended batch, zero full sweeps — is
        asserted independently of the query-side launch accounting."""
        self.counts["append_sweeps"] += 1
        self.counts["entries"] += int(Xq.shape[0]) * self.n
        inner_call = getattr(self.inner, "append_cross", self.inner.cross)
        out = inner_call(Xq, Vs)
        self._attribute(getattr(self.inner, "_last_sweep_route",
                                "dense_rows"))
        return out

    def map_row_panels(self, fn, block_size: Optional[int] = None):
        self._count_sweep(block_size)
        self._in_sweep = True
        try:
            return self.inner.map_row_panels(fn, block_size)
        finally:
            self._in_sweep = False
