"""Share of the traced window in which no operation ran, as the mean over
the cell's chips (each chip's share is logged).  Moves ``products_per_s``."""


def read(ctx):
    w = ctx.trace.window_s
    shares = {d: 100.0 * (1.0 - ctx.trace.busy_s[d] / w) for d in ctx.devices}
    ctx.log("idle share per chip (%): "
            + " ".join(f"{d}:{v:.4f}" for d, v in shares.items()))
    return sum(shares.values()) / len(shares)
