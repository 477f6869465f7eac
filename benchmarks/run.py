"""Benchmark harness: one entry per paper table/figure + framework extras.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call is '-' for
simulation benchmarks whose deliverable is the derived statistics).

  fig3        — delay vs rows, Scenarios 1/2 (paper Fig. 3)
  fig4        — delay vs rows, mu in {1,3,9} (paper Fig. 4)
  fig5        — CCP vs best/naive gaps on slow links (paper Fig. 5)
  fig_churn   — delay/efficiency under i.i.d./burst/cell-outage churn
                (beyond-paper, §1 claim; includes naive+oracle-timer)
  fig_decode  — measured LT decode overhead + counter-vs-decoder honesty
                gap across a loss sweep (beyond-paper, PR-4 decoder loop)
  fig_fleet   — multi-tenant saturation sweep: p50/p99 sojourn, helper
                utilization and Jain fairness vs offered load
                (beyond-paper, PR-7 fleet engine)
  fig_transport — delay/efficiency vs mean feedback RTT across iid/burst/
                cell churn; the price of delayed ACK/NACK observation
                (beyond-paper, PR-8 transport layer)
  efficiency  — measured vs eq.(12) efficiency (paper §6 table)
  overhead    — fountain codec failure prob + O(R) timing (paper §2 claims)
  kernel      — Pallas hot-spot roofline accounting + batched-MC speedup
  roofline    — aggregate the dry-run cells (EXPERIMENTS.md §Roofline)

Run everything:  PYTHONPATH=src python -m benchmarks.run
Subset:          PYTHONPATH=src python -m benchmarks.run --only fig3,fig5
Fast smoke:      PYTHONPATH=src python -m benchmarks.run --fast
Test-lane smoke: PYTHONPATH=src python -m benchmarks.run --smoke --only fig_churn
Device-sharded:  PYTHONPATH=src python -m benchmarks.run --shard --reps 64
Policy subset:   PYTHONPATH=src python -m benchmarks.run --only fig_churn \
                     --policies ccp,hcmm,adaptive_rate

``--policies`` routes any subset of registered policies (see
``repro.core.policies.names()``) through the figure sweeps; the ``--smoke``
lane defaults to *every* registered policy so a policy that breaks under
jit/vmap fails the fast test lane.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--fast", action="store_true",
                    help="reduced rep counts (CI smoke)")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal scale — the fast '-m \"not slow\"' test "
                         "lane runs this; implies tiny sweeps")
    ap.add_argument("--reps", type=int, default=None,
                    help="override the Monte-Carlo rep count per point")
    ap.add_argument("--shard", action="store_true",
                    help="shard MC key batches over the local devices "
                         "(engine.Engine(shard=True))")
    ap.add_argument("--policies", default=None,
                    help="comma-separated registered policy names to sweep "
                         "(default: per-figure defaults; --smoke defaults "
                         "to every registered policy)")
    args = ap.parse_args(argv)

    from repro.core import policies as policy_registry
    from repro.utils import compile_cache

    compile_cache.enable()

    from . import (efficiency, fig3, fig4, fig5, fig_churn, fig_decode,
                   fig_fleet, fig_transport, kernel_bench, overhead,
                   roofline_report)

    reps_explicit = args.reps is not None
    reps = args.reps if reps_explicit else (
        2 if args.smoke else (8 if args.fast else 40))
    shard = args.shard
    if args.policies is not None:
        swept = tuple(args.policies.split(","))
        for p in swept:
            policy_registry.get(p)  # fail loudly on typos, with known names
    else:
        # The smoke lane sweeps every registered policy through the churn
        # figure so a policy that breaks under jit/vmap fails the fast lane.
        swept = policy_registry.names() if args.smoke else None
    churn_policies = {} if swept is None else dict(policies=swept)
    fig_policies = {} if args.policies is None else dict(
        policies=tuple(p for p in swept))
    if args.smoke:
        sweep = (500,)
        churn_kw = dict(
            sweeps={name: ((axis[0], axis[-1]), mk, ax_name)
                    for name, (axis, mk, ax_name) in fig_churn.SWEEPS.items()},
            R=200, n_helpers=20,
        )
        decode_kw = dict(sweep=(0.0, 0.2), R=200, n_helpers=16,
                         offline_trials=2)
        fleet_kw = dict(task_sweep=(1, 4), R=120, n_helpers=10,
                        helpers_per_task=3, policies=("ccp", "naive"))
        transport_kw = dict(rtt_sweep=(0.0, 4.0), R=200, n_helpers=16)
    elif args.fast:
        sweep = (500, 1000)
        churn_kw = dict(
            sweeps={name: ((axis[0], axis[2]), mk, ax_name)
                    for name, (axis, mk, ax_name) in fig_churn.SWEEPS.items()},
        )
        decode_kw = dict(sweep=(0.0, 0.2), offline_trials=4)
        fleet_kw = dict(task_sweep=(1, 4, 8), R=200, n_helpers=12,
                        helpers_per_task=4)
        transport_kw = dict(rtt_sweep=(0.0, 1.0, 4.0), R=400, n_helpers=25)
    else:
        sweep = (1000, 2000, 4000, 8000)
        churn_kw = {}
        decode_kw = {}
        fleet_kw = {}
        transport_kw = {}
    small = args.fast or args.smoke
    # An explicit --reps is honored verbatim everywhere; the per-figure
    # scaling below only applies to the lane defaults.
    fig5_reps = reps if reps_explicit else max(reps // 2, 2 if small else 5)
    eff_reps = reps if reps_explicit else (min(reps, 4) if small else 20)
    jobs = {
        "fig3": lambda: fig3.run(reps=reps, r_sweep=sweep, shard=shard,
                                 **fig_policies),
        "fig4": lambda: fig4.run(reps=reps, r_sweep=sweep, shard=shard,
                                 **fig_policies),
        "fig5": lambda: fig5.run(reps=fig5_reps,
                                 r_sweep=(200, 400) if small
                                 else (200, 400, 800, 1600), shard=shard,
                                 **fig_policies),
        "fig_churn": lambda: fig_churn.run(reps=reps, shard=shard,
                                           **churn_policies, **churn_kw),
        "fig_decode": lambda: fig_decode.run(reps=reps, shard=shard,
                                             **decode_kw),
        "fig_fleet": lambda: fig_fleet.run(reps=reps, **fleet_kw),
        "fig_transport": lambda: fig_transport.run(reps=reps, shard=shard,
                                                   **fig_policies,
                                                   **transport_kw),
        "efficiency": lambda: efficiency.run(
            reps=eff_reps,
            R=400 if args.smoke else (2000 if args.fast else 8000),
            shard=shard),
        "overhead": overhead.run,
        "kernel": kernel_bench.run,
        "roofline": roofline_report.run,
    }
    only = set(args.only.split(",")) if args.only else set(jobs)
    failed = []
    print("name,us_per_call,derived")
    for name, job in jobs.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            job()
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
