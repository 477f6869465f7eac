"""Device time under the named scope ``moe.route`` (router, top-k, column
tables and the gather of the input's columns) per product, in the traced
window.  Moves ``products_per_s``."""

from chipbench import moe_trace


def read(ctx):
    return moe_trace.ms_per_product(ctx, ("moe.route",))
