"""A turn's required FLOPs (``required_work``: every session's ``steps``
decoded tokens through the routed experts, attention, head and landmark
fold-and-read) over build_s × chips × the bf16 peak, in %."""


def read(ctx):
    return 100.0 * ctx["work"]["turn_flops"] / (
        ctx["e2e"]["build_s"] * ctx["chips"] * ctx["peaks"].bf16_flops)
