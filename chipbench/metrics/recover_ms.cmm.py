"""Mean host-clock time of the collector's recovery (``coded_matmul.recover``
up to the recovered product being ready) per request in the traced window.
Moves ``product_p95_ms``."""


def read(ctx):
    d = ctx.spans.durations("recover")
    if not d:
        return None
    return 1e3 * sum(d) / len(d)
