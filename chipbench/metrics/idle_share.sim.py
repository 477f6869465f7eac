"""Share of the traced window in which no operation ran on the chip
(1 - busy / window, busy the union of the operations' intervals).  Moves
``reps_per_s``."""


def read(ctx):
    dev = ctx.devices[0]
    return 100.0 * (1.0 - ctx.trace.busy_s[dev] / ctx.trace.window_s)
