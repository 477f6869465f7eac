"""Device time of the batch program's operations under the named scope
``completion`` (the policy's completion rule, its sort of arrival times,
and the per-helper statistics), per 1000 reps run in the traced window,
counted as in ``scan_device_ms_per_krep.sim``.  Moves ``reps_per_s``."""

from chipbench import program_trace as pt


def read(ctx):
    return pt.sim_scope_ms_per_krep(ctx, "completion")
