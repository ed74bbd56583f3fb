"""Host-to-device bytes the block cache moved per query
(``ExecStats.device_bytes_h2d``, summed over the traced queries)."""

LAYER = "device block cache"
UNIT = "B/query"
MOVES = "qps"


def read(ctx):
    if not ctx.queries:
        return None
    return sum(q["stats"]["device_bytes_h2d"] for q in ctx.queries) \
        / len(ctx.queries)
