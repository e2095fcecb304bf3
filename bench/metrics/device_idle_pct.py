"""The device's idle share of the traced window: 1 minus the union of the
intervals in which an operation ran, over the window."""


def read(ctx):
    if not ctx.device.window_ns:
        return None
    return 100.0 * (1.0 - ctx.device.busy_ns / ctx.device.window_ns)
