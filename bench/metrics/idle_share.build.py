"""1 − (union of device op intervals) / window on the busiest device, in %,
over a window of certified builds."""
from bench.metrics._common import idle_share


def read(ctx):
    return idle_share(ctx)
