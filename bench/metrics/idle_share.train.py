"""Share of the training window in which no operation ran on the device,
from the profiler trace (1 - busy / window, in percent)."""


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * s.idle_share
