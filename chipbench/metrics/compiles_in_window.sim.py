"""Backend compiles inside the measured window (a certification doubling of
the horizon M compiles a new batch program).  Moves ``reps_per_s``."""


def read(ctx):
    return float(ctx.compiles_in_window)
