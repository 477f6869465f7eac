"""Faults planted under the timed path, and a small-size run of a cell
that has them: the check has to come out not correct.

  python -m chipbench.tests.faults <workload> <fault>   # prints correct

Used by ``test_faults.py``; the four-chip cell runs in a subprocess with
four virtual CPU devices.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time

SMALL = {
    "sim": {"config": {"N": 10, "R": 200},
            "traffic": {"reps_per_batch": 8, "check_reps": 4}},
    "cmm": {"config": {"hidden_size": 256, "intermediate_size": 512,
                       "num_hidden_layers": 3},
            "traffic": {"tokens": 128, "block_rows": 32, "x_pool": 2,
                        "check_requests": 3}},
}


def _patch(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the duration of the block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import coded_matmul as cm
    from repro.core import engine, simulator
    from repro.core.policies.ccp import CCPPolicy

    undo = []
    if fault == "sim_state_unchanged":
        # The policy's step hands its state on unchanged: no estimate ever
        # forms, so every helper falls back to stop-and-wait.
        undo.append(_patch(CCPPolicy, "on_computed", lambda self, s, ctx: s))
    elif fault == "sim_half_batch":
        run = engine._sim_batch_jit

        def half(keys, cfg, R, M, policy):
            h = keys.shape[0] // 2
            out = run(keys[:h], cfg=cfg, R=R, M=M, policy=policy)
            return {k: jnp.concatenate([v, v[: keys.shape[0] - h]])
                    for k, v in out.items()}
        undo.append(_patch(engine, "_sim_batch_jit", half))
    elif fault == "sim_answer_altered":
        done = simulator.completion_time

        def late(tr, k, tx_end=None):
            t, valid = done(tr, k, tx_end)
            return t * 1.001, valid
        undo.append(_patch(simulator, "completion_time", late))
    elif fault == "cmm_state_unchanged":
        # The kernel leaves its output buffer as it found it.
        run = cm.run
        undo.append(_patch(cm, "run", lambda plan, a, x, **kw:
                           jnp.zeros_like(run(plan, a, x, **kw))))
    elif fault == "cmm_half_batch":
        run = cm.run

        def half(plan, a, x, **kw):
            out = run(plan, a, x, **kw)
            return out.at[out.shape[0] // 2:].set(0)
        undo.append(_patch(cm, "run", half))
    elif fault == "cmm_no_exchange":
        # The collector keeps what its own chip computed; the survivors'
        # results on the other chips never reach it.
        recover = cm.recover

        def local(plan, out, survivors):
            d0 = out.sharding.mesh.devices.flat[0] if hasattr(
                out.sharding, "mesh") else None
            keep = np.zeros(out.shape[0], bool)
            for s in out.addressable_shards:
                if d0 is None or s.device == d0:
                    keep[s.index[0]] = True
            out = jnp.where(jnp.asarray(keep)[:, None], out, 0)
            return recover(plan, out, survivors)
        undo.append(_patch(cm, "recover", local))
    elif fault == "cmm_answer_altered":
        recover = cm.recover
        undo.append(_patch(cm, "recover", lambda plan, out, s:
                           recover(plan, out, s).at[0, 0].add(1.0)))
    else:
        raise ValueError(f"unknown fault {fault!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        for u in reversed(undo):
            u()
        jax.clear_caches()


def run_small(workload: str, fault: str = None, seed: int = 2 ** 31 + 11,
              seconds: float = 0.5) -> dict:
    """A run of ``workload`` at a small size on the CPU, with ``fault``."""
    from chipbench import run as R

    kind = workload.split(".")[0]
    ctx = planted(fault) if fault else contextlib.nullcontext()
    with ctx:
        return R.run(R.load_benchmark(), workload, seed, seconds, False,
                     require_tpu=False, t_start=time.perf_counter(),
                     overrides=SMALL[kind])


if __name__ == "__main__":
    fault = sys.argv[2] if len(sys.argv) > 2 and sys.argv[2] != "none" else None
    out = run_small(sys.argv[1], fault)
    print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
