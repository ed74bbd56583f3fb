"""Device program executions per query: the trace's module launches over
the queries completed in the traced window."""

LAYER = "streaming driver"
UNIT = "launches/query"
MOVES = "qps"


def read(ctx):
    t = ctx.trace
    if t is None or t.launches <= 0 or not ctx.queries:
        return None
    return t.launches / len(ctx.queries)
