"""Host time of the program's ``moe.size`` span per product: the one
readback of a request, the held experts' assignment counts, which picks
the column buffer.  It waits for the routing program to finish.  Moves
``product_p95_ms``."""

from chipbench import moe_trace


def read(ctx):
    return moe_trace.span_ms_per_product(ctx, "moe.size")
