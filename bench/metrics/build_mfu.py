"""The whole build's required FLOPs (``required_work``) over build_s × chips
× the bf16 peak, in %: what bounds any claim on build_s, whichever kernels
a build runs."""


def read(ctx):
    flops = ctx["work"]["build_flops"]
    return 100.0 * flops / (ctx["e2e"]["build_s"] * ctx["chips"]
                            * ctx["peaks"].bf16_flops)
