"""Device idle time inside the program's ``recover`` spans per product,
both products; the spans are put on the trace's clock by the harness
spans that both clocks hold.  Logs the window's idle time by the
innermost program span it fell in.  Moves ``products_per_s``."""

from chipbench import moe_trace
from chipbench import program_trace as pt


def read(ctx):
    ns = pt.recover_idle(ctx)
    calls = moe_trace.recover_calls_per_product(ctx)
    if ns is None or calls is None:
        return None
    return ns / 1e6 * calls
