"""Device time under the named scopes ``moe.swiglu`` (the activation between
the two coded products) and ``moe.combine`` (each token's weighted columns
into the output) per product, in the traced window.  Moves
``products_per_s``."""

from chipbench import moe_trace


def read(ctx):
    return moe_trace.ms_per_product(ctx, ("moe.swiglu", "moe.combine"))
