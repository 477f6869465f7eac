"""Host time of the recovery's device work per request: the program's
spans ``recover.gather`` (survivor slices and their concatenation),
``decode.convert`` (the payload's f32 conversion) and ``decode.solve``
(the HIGHEST matmul and reshapes) inside each ``recover`` span, in the
traced window.  Logs the ``recover`` span's mean and its self
time beside it.  Moves ``product_p95_ms``."""

from chipbench import program_trace as pt

SPANS = ("recover.gather", "decode.convert", "decode.solve")


def read(ctx):
    v = pt.child_ms_per_request(ctx, SPANS)
    reqs = pt.recover_requests(ctx)
    if v is None or not reqs:
        return None
    root = 1e3 * sum(r.t1 - r.t0 for r, _ in reqs) / len(reqs)
    kids = pt.child_ms_per_request(ctx, {c.name for _, k in reqs for c in k})
    ctx.log(f"program recover span: {len(reqs)} calls, mean {root:.4f} ms, "
            f"child spans {kids:.4f} ms, self {root - kids:.4f} ms")
    return v
