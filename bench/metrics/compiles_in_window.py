"""Programs compiled, or loaded from the persistent cache, during the
window (a ``jax.monitoring`` listener the harness installs); warm-up
should leave none."""


def read(ctx):
    return float(ctx.compiles)
