"""Dense recoveries (the program's ``decode.dense`` counter: a host
pseudo-inverse each) in the traced window, per product, both products;
0 where every loss takes the peel path.  Moves ``product_p95_ms``."""

from chipbench import program_trace as pt


def read(ctx):
    dense = pt.count_events(ctx, "decode.dense")
    if dense is None or not ctx.result["attempted"]:
        return None
    ctx.log(f"recoveries in the window: {dense} dense, "
            f"{pt.count_events(ctx, 'decode.peel')} peeled")
    return dense / ctx.result["attempted"]
