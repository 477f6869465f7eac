#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json``, one run.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run builds the cell's state on the device from the seed, warms up every
shape the cell's traffic uses (set-up), drives the traffic for ``--seconds``
(the window), and checks what the window produced against the plain
reference.  Its last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, last, ``checks``: each number compared with its limit.  The
same numbers are the last lines on standard error.

Everything is found by name: the cell's configuration (``file`` of its
entry in ``BENCHMARK.json``, whose ``generator`` names a traffic generator
in ``generators/``), its traffic mix (``traffic/<mix>.json``), the limits of its
check (``limits/<cell>.json``) and each per-layer metric's reader
(``metrics/<metric>.py``).  A machine whose JAX finds no TPU, or fewer
chips than the cell asks for, ends the run with an error and no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"


def _log(*parts):
    print("chipbench:", *parts, file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(bench: dict, workload: str) -> dict:
    """The cell, its configuration, traffic, limits and metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    read = lambda p: json.loads(pathlib.Path(p).read_text())
    in_cell = lambda m: workload in m.get("workloads", [workload])
    return dict(
        cell=cell,
        config=read(ROOT / entry["file"]),
        traffic=read(HERE / "traffic" / f"{cell['traffic']}.json"),
        limits=read(HERE / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if in_cell(m)],
        per_layer=[m for m in bench["per_layer"] if in_cell(m)])


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Context:
    """What a per-layer metric's reader is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def log(self, *parts):
        _log(*parts)


def run(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
        *, require_tpu: bool = True, t_start: float = None,
        overrides: dict = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_tpu=False`` and ``overrides`` (keys updated in the loaded
    ``config``, ``traffic`` and ``limits``) serve the tests, which drive a
    run at a small size on the CPU."""
    t_start = T_START if t_start is None else t_start
    spec = resolve(bench, workload)
    for part, upd in (overrides or {}).items():
        spec[part] = dict(spec[part], **upd)
    chips = int(spec["cell"]["chips"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from chipbench import peaks as peaks_mod
    from chipbench import trace as trace_mod
    from chipbench.compile_clock import CompileClock
    from chipbench.spans import Spans

    enable_compile_cache()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU found; JAX sees "
                         f"{len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: {workload} needs {chips} chips, JAX "
                         f"sees {len(devices)}")
    kind = devices[0].device_kind
    peaks = peaks_mod.peaks(kind) if require_tpu else None
    clock = CompileClock().register()
    spans = Spans()

    generator = importlib.import_module(
        f"chipbench.generators.{spec['config']['generator']}")
    wl = generator.Workload(spec["config"], spec["traffic"], spec["limits"],
                         seed, chips, spans)
    wl.warm()
    setup_s = time.perf_counter() - t_start
    _log(f"setup_s={setup_s:.4f} compile_s={clock.seconds:.4f} "
         f"compiles={clock.compiles}")

    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(logdir)
        spans.tracing = True
    c0 = clock.compiles
    with spans.span("window"):
        res = wl.window(seconds)
    compiles_in_window = clock.compiles - c0
    used = [d.id for d in wl.devices]
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        spans.tracing = False
        path = trace_mod.find_xplane(logdir)
        _log(f"trace {os.path.getsize(path)} bytes")
        reduced = trace_mod.reduce(trace_mod.read(path), used)
        shutil.rmtree(logdir, ignore_errors=True)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in wl.devices]
    peak = max((m for m in mem if m is not None), default=None)
    _log(f"window {res['seconds']:.4f} s, attempted {res['attempted']}, "
         f"failed {res['failed']}, compiles in window {compiles_in_window}, "
         f"work {json.dumps(res['work'])}, peak bytes per chip {mem}")

    wl.release()
    gc.collect()
    t_check = time.perf_counter()
    checks = wl.check()
    _log(f"check took {time.perf_counter() - t_check:.4f} s")

    metrics = {}
    if not trace:
        values = dict(res["metrics"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = Context(workload=workload, trace=reduced, spans=spans,
                      result=res, work=res["work"], devices=used,
                      chips=chips, peaks=peaks,
                      compiles_in_window=compiles_in_window)
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": checks.correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = sum(reduced.busy_s.values()) / len(used)
        device["window_s"] = reduced.window_s
        out["breakdown"] = trace_mod.breakdown(reduced.trace, reduced.w0,
                                               reduced.w1)
    out["checks"] = checks.as_dict()
    for name, v in out["checks"].items():
        _log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(load_benchmark(), args.workload, args.seed, args.seconds,
              bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
