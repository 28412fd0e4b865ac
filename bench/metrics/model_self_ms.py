"""Device milliseconds per build outside the sweep kernel.

The busiest device's busy time in the window (the union of its op
intervals) less the sweep kernel's device time per device, found by its
launch record (``_named.sweep_launches``), per build.  This is the model
layer's own work (``spsd.select``, ``spsd.sketch_block``, ``spsd.fast_u``,
``spsd.certify``) plus the sweep's set-up around its kernel (the one-hot
gather columns, the probes' padding) and the relayout of X.  The sum over
ops under the ``spsd.*`` scopes alone read 10% (SUSY) and 16% (MNIST) lower
on a v5e: those last two run outside every ``spsd.*`` scope (PERF.md §5).
"""
from bench.metrics import _named


def read(ctx):
    found = _named.sweep_launches(ctx["trace"])
    if not found:
        return None
    red = ctx["trace"]
    sweep_s = sum(secs for _, _, secs, _ in found) / ctx["chips"]
    return 1000.0 * (max(red.busy_s.values()) - sweep_s) \
        / ctx["counters"]["builds"]
