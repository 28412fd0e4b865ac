"""The turn's roofline time over build_s, in %.

Per step, the larger of its required FLOPs over the bf16 peak and its
required bytes over the HBM bandwidth; the bytes are the weights of the
experts its layers routed at least one token to (from the program's own
per-step dispatch counts, ``experts_touched``), every attention, router and
norm weight, the head, the window rings and the landmark factors
(``required_work``).  Summed over the turn's steps."""


def read(ctx):
    work, pk = ctx["work"], ctx["peaks"]
    roof = sum(max(work["step_flops"] / pk.bf16_flops,
                   (work["static_step_bytes"] + n * work["expert_bytes"])
                   / pk.hbm_bytes_per_s)
               for n in ctx["counters"]["experts_touched"])
    return 100.0 * roof / (ctx["e2e"]["build_s"] * ctx["chips"])
