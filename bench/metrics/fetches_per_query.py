"""Arrays brought from the device to the host per query: the mean of the
``fetches`` arg (the ``device.fetches`` counter's delta) of the
``bench.query`` spans; a query that fetched nothing carries no arg and
counts 0. None when no query span carries the arg."""

QUERY = "bench.query"


def read(ctx):
    queries = [e for e in ctx.spans if e["name"] == QUERY]
    if not queries or not any("fetches" in e.get("args", {})
                              for e in queries):
        return None
    return sum(e["args"].get("fetches", 0) for e in queries) / len(queries)
