"""Programs compiled or fetched from the compile cache inside the measured
window, per product (the collector's peeling recovery traces and compiles
its decode scan on each call).  Moves ``product_p95_ms``."""


def read(ctx):
    n = ctx.result["attempted"]
    if n == 0:
        return None
    return ctx.compiles_in_window / n
