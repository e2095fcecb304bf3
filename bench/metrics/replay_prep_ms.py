"""Replay staging: host ms per query inside ``sim.prepare`` (gathering the
compositions' columns and staging the slots, inside ``sim.rerank`` and
before ``sim.replay``)."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "sim.prepare"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("sim.prepare") * 1e3 / ctx.queries
