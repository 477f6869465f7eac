"""Shared helpers for the paper-figure benchmarks."""

from __future__ import annotations

import json
import os
import pathlib
from typing import Callable, Dict, List

import numpy as np

# BENCH_OUT_DIR overrides the artifact directory (the smoke-test lane points
# it at a tmpdir so tiny-scale runs never clobber the committed artifacts).
OUT_DIR = pathlib.Path(
    os.environ.get(
        "BENCH_OUT_DIR",
        pathlib.Path(__file__).resolve().parent.parent / "experiments" / "bench",
    )
)


def _stats(a: np.ndarray) -> Dict[str, float]:
    return {"mean": float(a.mean()), "std": float(a.std()),
            "sem": float(a.std() / np.sqrt(len(a)))}


def mc(fn: Callable, cfg, R: int, reps: int, seed0: int = 0) -> Dict[str, float]:
    """Sequential Monte-Carlo mean/std of fn(key, cfg, R)["T"] over ``reps``
    draws.  Kept for the numpy-driven baseline reference paths; the figure
    benchmarks go through the vmapped :func:`mc_policy` instead.  Keys come
    from the same fold_in schedule, so baseline and policy rows in one
    figure share helper draws rep-for-rep."""
    from repro.core import simulator

    keys = simulator.batch_keys(reps, seed0)
    ts = []
    for r in range(reps):
        ts.append(fn(keys[r], cfg, R)["T"])
    return _stats(np.asarray(ts))


def certified(out: Dict, label: str) -> np.ndarray:
    """The certification mask of an ``Engine.run`` result, as the one shared
    drop-the-invalid-reps gate: raises when *no* rep is certified (horizon
    cap hit for the whole batch), otherwise returns the boolean mask the
    caller must apply before aggregating (counting ``~mask`` as invalid)."""
    valid = np.asarray(out["valid"])
    if not valid.any():
        raise RuntimeError(
            f"{label}: no certified rep at horizon cap (M={out['M']}) — "
            "churn config too hostile?"
        )
    return valid


def mc_policy(cfg, R: int, reps: int, policy: str, seed0: int = 0,
              shard: bool = False) -> Dict[str, float]:
    """Batched Monte-Carlo over ``reps`` vmapped keys via the policy engine
    (one compile + one device call instead of ``reps`` sequential runs);
    ``policy`` is any registered name — ``ccp``, ``best``, ``naive``,
    ``naive_oracle``, ``uncoded_mean``/``uncoded_mu``, ``hcmm``,
    ``adaptive_rate``, ... Uncertified reps (horizon cap hit under heavy
    churn -> T possibly inf or understated) are excluded from the stats and
    counted in ``invalid``.  ``shard=True`` splits the key batch over the
    local devices."""
    from repro.core import engine, simulator

    out = engine.Engine(shard=shard).run(
        cfg, policy, simulator.batch_keys(reps, seed0), R)
    valid = certified(out, f"mc_policy policy={policy!r} R={R}")
    stats = _stats(np.asarray(out["T"])[valid])
    stats["invalid"] = int((~valid).sum())
    return stats


def policy_meta(names) -> Dict[str, int]:
    """``meta.policy`` entry for bench artifacts: registry name -> version
    for every policy the run swept (artifact rows from different policy
    implementations are never compared silently)."""
    from repro.core import policies

    return {n: policies.get(n).version for n in names}


def emit(name: str, rows: List[dict], derived: str = "",
         policies: Dict[str, int] | None = None,
         extra_meta: Dict[str, object] | None = None) -> None:
    """Write JSON artifact + the harness CSV line ``name,us_per_call,derived``.

    The artifact is ``{"meta": {...}, "data": rows}``: ``meta`` records the
    PRNG key schedule (PR 2 switched batch_keys from the collision-prone
    ``seed0*100003 + r`` arithmetic to ``fold_in``) and — for policy sweeps
    — ``meta.policy``, the registry name -> version map from
    :func:`policy_meta`, plus ``meta.decoder``, marking per policy whether
    its completion rule actually *decodes* in the loop (``"in_loop"``) or
    counts packets (``"counter"``), so delay trajectories from the two
    completion semantics are never compared silently.  ``extra_meta``
    merges figure-specific keys (e.g. fig_fleet's ``discipline``)."""
    from repro.core import policies as policy_registry
    from repro.core import simulator

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    meta = {"key_schedule": simulator.KEY_SCHEDULE}
    if policies:
        meta["policy"] = dict(policies)
        meta["decoder"] = {
            n: ("in_loop" if policy_registry.get(n).uses_decoder
                else "counter")
            for n in policies
        }
    if extra_meta:
        meta.update(extra_meta)
    doc = {"meta": meta, "data": rows}
    (OUT_DIR / f"{name}.json").write_text(json.dumps(doc, indent=1))
    print(f"{name},-,{derived}")

