"""The whole step's share of the chips' bf16 peak: the useful operations of
the source products completed in the traced window (2 rows k n each;
parities and recovery do not count), over the window's seconds, the chips
and the peak.  Moves ``products_per_s``."""


def read(ctx):
    done = ctx.work["products"] * ctx.work["product_flops"]
    return 100.0 * done / (ctx.result["seconds"] * ctx.chips
                           * ctx.peaks["bf16_flops"])
