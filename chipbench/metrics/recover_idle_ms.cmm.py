"""Device idle time inside the program's ``recover`` spans, per request,
as the mean over the cell's chips; the spans are put on the trace's clock
by the harness spans that both clocks hold.  Logs the window's idle time
by the innermost program span it fell in.  Moves ``products_per_s``."""

from chipbench import program_trace as pt


def read(ctx):
    ns = pt.recover_idle(ctx)
    return None if ns is None else ns / 1e6
