"""The sweep kernel's device time over the window's wall time, in %."""
from bench.metrics._common import per_device, sweep_pattern


def read(ctx):
    found = per_device(ctx, sweep_pattern(ctx))
    if found is None:
        return None
    return 100.0 * found[1] / ctx["win"]["window_s"]
