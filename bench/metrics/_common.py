"""Shared arithmetic of the per-layer readers.

Kernels carry no names of their own, and a TPU trace names each operation
by its HLO text, so the readers match that text.  Every Pallas launch is a
``tpu_custom_call``.  In a certify cell the sweep is the one that returns a
tuple of (n × ·) blocks, C and K Z; in a serving cell every Pallas launch is
a cross launch.
"""
from __future__ import annotations

PALLAS = r'custom_call_target="tpu_custom_call"'


def sweep_pattern(ctx) -> str:
    """The fused sweep: a Pallas launch whose result is a tuple whose
    first block has the corpus's n rows."""
    n = int(ctx["cell"].config["n"])
    return rf"= \(f32\[{n},\d+\][^=]*{PALLAS}"


def per_device(ctx, pattern):
    """(launches, seconds per device) of the ops matching ``pattern``, or
    None when the trace has none."""
    count, secs = ctx["trace"].op_seconds(pattern)
    if count == 0:
        return None
    return count / ctx["chips"], secs / ctx["chips"]


def idle_share(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - max(red.busy_s.values()) / red.window_s)
