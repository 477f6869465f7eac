"""Host time of the program's ``recover`` spans per product, both products
(gate/up and down, told apart by the span's ``product`` attribute in the
log), in the traced window.  Moves ``product_p95_ms``."""

from chipbench import moe_trace


def read(ctx):
    return moe_trace.span_ms_per_product(ctx, "recover")
