"""Host time of the recoveries' planning per product, both products: the
program's spans ``decode.peel_plan``, ``decode.rank`` and ``decode.pinv``
(peel plan, and on the dense path the rank test and the host
pseudo-inverse) inside its ``recover`` spans, in the traced window.
Moves ``product_p95_ms``."""

from chipbench import moe_trace

SPANS = ("decode.peel_plan", "decode.rank", "decode.pinv")


def read(ctx):
    return moe_trace.recover_child_ms_per_product(ctx, SPANS)
