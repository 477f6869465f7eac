"""Dense recoveries (the program's ``decode.dense`` counter: a host
pseudo-inverse each) in the traced window, per product.  Moves
``product_p95_ms``."""

from chipbench import program_trace as pt


def read(ctx):
    n = pt.count_events(ctx, "decode.dense")
    if n is None or not ctx.result["attempted"]:
        return None
    return n / ctx.result["attempted"]
