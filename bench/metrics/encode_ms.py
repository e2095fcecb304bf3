"""Config encoding: host ms per query inside ``api.encode`` spans, at every
depth (the table's encoding before ``api.characterize``, and each swept
point's inside ``hetero.expand``)."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "api.encode"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("api.encode") * 1e3 / ctx.queries
