"""The landmark-attention kernel's share of its roofline, in %.

The least time the chip could take for the window's landmark launches —
per launch the larger of its required FLOPs over the bf16 peak and its
required bytes over the HBM bandwidth (``required_work``: the fold and the
read of every (session, KV head) factor set; k_land and Ũ read, UV and U1
left on chip) — over the launches' device time in the trace.  The byte
term binds.
"""
from bench.metrics import _landmark


def read(ctx):
    found = _landmark.launches(ctx["trace"])
    if found is None:
        return None
    count, secs = found
    work, pk = ctx["work"], ctx["peaks"]
    roof = max(work["landmark_flops"] / pk.bf16_flops,
               work["landmark_bytes"] / pk.hbm_bytes_per_s)
    return 100.0 * roof * count / secs
