"""Host time of the recovery's planning per request: the program's spans
``decode.peel_plan``, ``decode.rank`` and ``decode.pinv`` (peel plan, rank
test, host pseudo-inverse and its upload) inside each ``recover`` span, in
the traced window.  Moves ``product_p95_ms``."""

from chipbench import program_trace as pt

SPANS = ("decode.peel_plan", "decode.rank", "decode.pinv")


def read(ctx):
    return pt.child_ms_per_request(ctx, SPANS)
