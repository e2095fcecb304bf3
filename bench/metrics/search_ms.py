"""Search: host ms per query inside ``hetero.search`` (branch-and-bound or
the exhaustive grid, scoring included)."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "hetero.search"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("hetero.search") * 1e3 / ctx.queries
