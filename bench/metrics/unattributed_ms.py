"""The query's unnamed host time: ms per query inside ``bench.query`` but
outside its direct children, found by thread, depth and containment as
``compose_self_ms`` finds its children (so it reads on a program whose
spans carry no parent ids too)."""

QUERY = "bench.query"


def read(ctx):
    queries = [e for e in ctx.spans if e["name"] == QUERY]
    if not ctx.queries or not queries:
        return None
    total = 0.0
    for q in queries:
        end = q["ts"] + q["dur"]
        inner = sum(e["dur"] for e in ctx.spans
                    if e["tid"] == q["tid"] and e["depth"] == q["depth"] + 1
                    and q["ts"] <= e["ts"] and e["ts"] + e["dur"] <= end)
        total += q["dur"] - inner
    return total / 1e3 / ctx.queries
