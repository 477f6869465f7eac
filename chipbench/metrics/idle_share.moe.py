"""Share of the traced window in which no operation ran on the cell's chip.
Moves ``products_per_s``."""


def read(ctx):
    (dev,) = ctx.devices
    return 100.0 * (1.0 - ctx.trace.busy_s[dev] / ctx.trace.window_s)
