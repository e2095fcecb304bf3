"""Composition scoring on the device: ms per query of the scorer's
programs (``jit_score_kernel``) in the profiler trace."""

SCORE_PROGRAM = r"score_kernel"


def read(ctx):
    if not ctx.queries:
        return None
    ns = ctx.device.program_ns(SCORE_PROGRAM)
    return ns / 1e6 / ctx.queries if ns else None
