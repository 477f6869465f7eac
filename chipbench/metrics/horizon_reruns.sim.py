"""Batches run again in the traced window because the shared horizon
doubled (the program's ``engine.horizon_rerun`` counter), whether the
doubled program compiled or came from the compile cache.  Moves
``reps_per_s``."""

from chipbench import program_trace as pt


def read(ctx):
    n = pt.count_events(ctx, "engine.horizon_rerun")
    return None if n is None else float(n)
