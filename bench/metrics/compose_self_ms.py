"""Host compose: ms per query inside ``hetero.compose`` but outside its
child spans ``hetero.expand``, ``hetero.search`` and ``sim.rerank``
(candidate lists, grid assembly, ordering, materializing the report)."""

CHILDREN = ("hetero.expand", "hetero.search", "sim.rerank")


def read(ctx):
    composes = [e for e in ctx.spans if e["name"] == "hetero.compose"]
    if not ctx.queries or not composes:
        return None
    total = 0.0
    for c in composes:
        end = c["ts"] + c["dur"]
        inner = sum(e["dur"] for e in ctx.spans
                    if e["name"] in CHILDREN and e["tid"] == c["tid"]
                    and e["depth"] == c["depth"] + 1
                    and c["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end)
        total += c["dur"] - inner
    return total / 1e3 / ctx.queries
