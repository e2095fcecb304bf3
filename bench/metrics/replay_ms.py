"""Trace replay: host ms per query inside ``sim.replay``."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "sim.replay"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("sim.replay") * 1e3 / ctx.queries
