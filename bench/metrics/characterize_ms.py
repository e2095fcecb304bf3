"""Characterization: host ms per query inside the ``api.characterize`` span
(the device work ends inside it, in the conversion to numpy)."""


def read(ctx):
    if not ctx.queries or not any(e["name"] == "api.characterize"
                                  for e in ctx.spans):
        return None
    return ctx.span_total_s("api.characterize") * 1e3 / ctx.queries
