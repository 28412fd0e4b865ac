"""The sweep kernel's share of its roofline, in %.

The least time the chip could take for the window's sweeps — the larger of
their required FLOPs over the bf16 peak and their required bytes over the
HBM bandwidth (``required_work``: 2n²(d + p) FLOPs, X and Z in, K Z out) —
over the sweep kernel's device time in the trace.  The FLOP term binds at
every configured size.
"""
from bench.metrics._common import per_device, sweep_pattern


def read(ctx):
    found = per_device(ctx, sweep_pattern(ctx))
    if found is None:
        return None
    _, secs = found
    builds = ctx["counters"]["builds"]
    work, pk = ctx["work"], ctx["peaks"]
    chips = ctx["chips"]
    roof = max(work["sweep_flops"] / pk.bf16_flops,
               work["sweep_bytes"] / pk.hbm_bytes_per_s) * builds / chips
    return 100.0 * roof / secs
