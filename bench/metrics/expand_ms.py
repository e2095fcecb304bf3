"""Operating-point expansion: host ms per query inside ``hetero.expand``."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "hetero.expand"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("hetero.expand") * 1e3 / ctx.queries
