"""Device time of the engine's batch program (the XLA module of
``_sim_batch_jit``: draws, policy scan, completion) per 1000 reps run in
the traced window.  Moves ``reps_per_s``."""

MODULE = "_sim_batch_jit"


def read(ctx):
    dev = ctx.devices[0]
    n, ns = ctx.trace.module(MODULE, dev)
    reps = ctx.result["attempted"]
    if n == 0 or reps == 0:
        return None
    ctx.log(f"{MODULE}: {n} runs, {ns / 1e9:.6f} s on device {dev}")
    return ns / 1e6 / (reps / 1000.0)
