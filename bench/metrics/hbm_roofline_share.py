"""Share of the HBM roofline the device steps reach: the traced queries'
logical bytes (rows x storage width of every column each references) at
the chip's peak bandwidth, over the device's busy time.  The steps do a
few operations per byte, so bandwidth, not the FLOP peak, bounds them."""

LAYER = "device steps"
UNIT = "%"
MOVES = "qps"


def read(ctx):
    t = ctx.trace
    if t is None or t.busy_s <= 0 or not ctx.queries:
        return None
    need_s = sum(q["logical_bytes"] for q in ctx.queries) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / t.busy_s
