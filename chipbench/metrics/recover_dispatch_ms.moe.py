"""Host time of the recoveries' device work per product, both products: the
program's spans ``recover.gather`` (survivor slices and their
concatenation), ``decode.apply`` (the peel plan's matrix and its matmul),
``decode.convert`` and ``decode.solve`` (the dense path's) inside its
``recover`` spans, in the traced window.  Moves ``product_p95_ms``."""

from chipbench import moe_trace

SPANS = ("recover.gather", "decode.apply", "decode.convert", "decode.solve")


def read(ctx):
    return moe_trace.recover_child_ms_per_product(ctx, SPANS)
