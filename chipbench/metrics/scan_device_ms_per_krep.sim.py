"""Device time of the batch program's operations under the named scope
``policy_stream`` (the CCP pacing scan), per 1000 reps run in the traced
window: the union of the intervals of the outermost operations, so a
``while`` and its body count once.  Moves ``reps_per_s``."""

from chipbench import program_trace as pt


def read(ctx):
    return pt.sim_scope_ms_per_krep(ctx, "policy_stream")
