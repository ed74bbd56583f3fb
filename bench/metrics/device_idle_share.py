"""Share of the traced window in which the device ran no operation
(1 - busy / window, from the profiler trace)."""

LAYER = "device"
UNIT = "%"
MOVES = "qps"


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
