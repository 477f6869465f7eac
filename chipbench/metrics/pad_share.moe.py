"""Share of the column buffers' columns that hold no token, over the
requests of the traced window: each expert group's padding to whole
column tiles, and the dead tiles past the last group.  The program
returns each request's routed and shared column counts as a small device
array, read after the window.  Moves ``products_per_s``."""


def read(ctx):
    real = sum(ctx.work["real_columns"])
    total = sum(ctx.work["buffer_columns"])
    if not total:
        return None
    return 100.0 * (total - real) / total
