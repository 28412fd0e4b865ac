"""The share of the sweep kernel's issued MXU work that the build requires,
in %.

The window's required sweep FLOPs (``required_work``: 2n²(d + p) per build,
times its builds) over the MXU FLOPs its sweep launches issued: each launch's
own record (``mxu_flops``, counted at its launch shapes, with the one-hot
gather of C and every right-hand side padded to 128 columns), times the
launch's events in the window.  The launches are found by the ``kernel`` of
their record.  ``sweep_roofline`` over this share is the rate at which the
kernel issues MXU work.
"""
from bench.metrics import _named


def read(ctx):
    found = _named.sweep_launches(ctx["trace"])
    if not found:
        return None
    issued = sum(count * int(rec["mxu_flops"]) for _, count, _, rec in found)
    required = ctx["work"]["sweep_flops"] * ctx["counters"]["builds"]
    return 100.0 * required / issued
