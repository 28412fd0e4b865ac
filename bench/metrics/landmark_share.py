"""The landmark-attention launches' device time over the busiest device's
busy time in the window, in %: the share of a decode turn that the full
layers' fold-and-read takes."""
from bench.metrics import _landmark


def read(ctx):
    found = _landmark.launches(ctx["trace"])
    if found is None:
        return None
    red = ctx["trace"]
    return 100.0 * found[1] / ctx["chips"] / max(red.busy_s.values())
