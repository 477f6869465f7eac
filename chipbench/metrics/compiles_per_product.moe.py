"""Programs compiled inside the measured window, per product: a column
buffer size that set-up did not compile would show here.  Moves
``product_p95_ms``."""


def read(ctx):
    n = ctx.result["attempted"]
    if n == 0:
        return None
    return ctx.compiles_in_window / n
