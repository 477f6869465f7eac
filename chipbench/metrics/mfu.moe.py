"""The whole step's share of the chip's bf16 peak: the useful operations of
the expert FFNs completed in the traced window, 2 (3 d f) per routed and
shared column (gate, up and down; parities, padding and recovery do not
count), over the window's seconds, the chips and the peak.  Moves
``products_per_s``."""


def read(ctx):
    done = sum(ctx.work["real_columns"]) * ctx.work["flops_per_column"]
    return 100.0 * done / (ctx.result["seconds"] * ctx.chips
                           * ctx.peaks["bf16_flops"])
