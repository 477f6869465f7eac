#!/usr/bin/env python3
"""Readings that set the limits of a cell's check: the program's on many
seeds, and the control's, in one process.

  python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 3 [--control-seeds 1,2,3]

For each seed the cell's traffic runs for a short window at its own sizes
and load, and the numbers of the check are read twice: once for what the
program produced (the lower readings), and once for the control, the plain
reference computed in the next precision below the configuration's, put in
the program's place (the upper readings).  One JSON line per seed and side.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import importlib

    import jax

    from chipbench import run as R
    from chipbench.spans import Spans

    R.enable_compile_cache()
    spec = R.resolve(R.load_benchmark(), args.workload)
    chips = int(spec["cell"]["chips"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"control: needs {chips} TPU chips, JAX sees "
                         f"{len(devices)} {devices[0].platform}")
    generator = importlib.import_module(
        f"chipbench.generators.{spec['config']['generator']}")
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        wl = generator.Workload(spec["config"], spec["traffic"], spec["limits"],
                             seed, chips, Spans())
        wl.warm()
        res = wl.window(args.seconds)
        wl.release()
        gc.collect()
        ck = wl.check()
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              side="program", attempted=res["attempted"],
                              failed=res["failed"], correct=ck.correct,
                              checks=ck.as_dict(),
                              seconds=time.perf_counter() - t0)), flush=True)
        if seed in ctrl:
            ck = wl.control()
            print(json.dumps(dict(workload=args.workload, seed=seed,
                                  side="control", correct=ck.correct,
                                  checks=ck.as_dict())), flush=True)
        del wl
        gc.collect()


if __name__ == "__main__":
    main()
