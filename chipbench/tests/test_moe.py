"""The coded expert-layer cell at a small size on the CPU: a sound run is
correct, each planted fault and the fp8 control are not, and the cell's
per-layer readers read a context made from a small run."""

import gc
import importlib

import pytest

from chipbench import moe_trace
from chipbench import program_trace as pt
from chipbench import run as R
from chipbench import trace as T
from chipbench.spans import Spans
from chipbench.tests.moe_faults import SMALL, WORKLOAD, run_small


@pytest.mark.parametrize("fault", [None, "moe_softmax", "moe_scale_dropped",
                                   "moe_no_shared", "moe_lost_shard_used",
                                   "moe_wrong_expert"])
def test_moe_cell_with_planted_faults(fault):
    out = run_small(fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0
    if fault == "moe_wrong_expert":
        # The check lays out the columns itself, so the coded products
        # alone show the wrong expert.
        coded = out["checks"]["coded_gap"]
        assert coded["value"] > coded["limit"], out["checks"]


def _small_run(trace_window=True):
    spec = R.resolve(R.load_benchmark(), WORKLOAD)
    config = dict(spec["config"], **SMALL["config"])
    traffic = dict(spec["traffic"], **SMALL["traffic"])
    gen = importlib.import_module("chipbench.generators.moe")
    spans = Spans()
    wl = gen.Workload(config, traffic, spec["limits"], 2 ** 31 + 5, 1, spans)
    wl.warm()
    with spans.span("window"):
        res = wl.window(0.5)
    wl.release()
    gc.collect()
    return wl, spans, res


def test_moe_control_fails_and_program_passes():
    wl, _, _ = _small_run()
    program, control = wl.check(), wl.control()
    assert program.correct, program.as_dict()
    assert not control.correct, control.as_dict()
    # The control fails by a margin, not by rounding at the limit.
    assert any(v > 3 * lim and lim > 0 for _, v, lim in control.items)


def test_moe_readers_on_a_small_cpu_run():
    """Readers on a context from a small run: a trace made to match, with
    one 100 ns operation of each scope inside each run of each program."""
    _, spans, res = _small_run()
    n = res["attempted"]
    programs = res["programs"]
    assert set(programs) == {"jit_moe_route", "jit_moe_gate_up",
                             "jit_moe_down", "jit_moe_combine"}
    (_, w0, w1), = [r for r in spans.records if r[0] == "window"]
    t0 = int(w0 * 1e9)
    ops, mods, want = [], [], {}
    for i, (module, texts) in enumerate(sorted(programs.items())):
        smap = pt.scope_map(texts[0], moe_trace.SCOPES)
        picks = {}
        for name, scope in smap.items():
            picks.setdefault(scope, name)
        start = t0 + 1000 * (i + 1)
        mods.append((f"{module}(7)", start, 900))
        for j, (scope, name) in enumerate(sorted(picks.items())):
            ops.append((name, start + 10 + 200 * j, 100))
            want[scope] = want.get(scope, 0) + 100
        ops.append(("coded_matmul.1", start + 800, 50))
    assert set(want) == set(moe_trace.SCOPES)
    traced = [(nm, int(a * 1e9), int((b - a) * 1e9))
              for nm, a, b in spans.records]
    trace = T.Trace({0: {T.OPS_LINE: sorted(ops, key=lambda e: e[1]),
                         T.MODULES_LINE: mods}}, traced)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = R.Context(workload=WORKLOAD, trace=T.reduce(trace, [0]),
                    spans=spans, result=res, work=res["work"], devices=[0],
                    chips=1, peaks=peaks, compiles_in_window=0)
    read = lambda name: R.load_reader(name)(ctx)
    assert read("route_device_ms.moe") == pytest.approx(
        want["moe.route"] / 1e6 / n)
    assert read("combine_device_ms.moe") == pytest.approx(
        (want["moe.swiglu"] + want["moe.combine"]) / 1e6 / n)
    assert read("compiles_per_product.moe") == 0.0
    assert 0 < read("recover_ms.moe") and 0 < read("size_sync_ms.moe")
    real, buf = sum(res["work"]["real_columns"]), sum(
        res["work"]["buffer_columns"])
    assert read("pad_share.moe") == pytest.approx(100 * (buf - real) / buf)
    assert read("mfu.moe") > 0
    assert 0 < read("idle_share.moe") < 100
    assert read("recover_plan_ms.moe") > 0
    assert read("recover_dispatch_ms.moe") > 0
    assert read("recover_idle_ms.moe") > 0
    dense = pt.count_events(ctx, "decode.dense")
    peeled = pt.count_events(ctx, "decode.peel")
    assert dense + peeled == 2 * n
    assert read("pinv_per_product.moe") == dense / n
    # Four kernel events of 50 ns: the least time over 200 ns.
    least = 0.0
    for c in res["work"]["real_columns"]:
        for p in res["work"]["kernel_products"]:
            least += max(
                2.0 * p["C"] * p["bm"] * p["k"] * c / peaks["bf16_flops"],
                p["itemsize"] * (p["E"] * p["rows"] * p["k"] + p["k"] * c
                                 + p["C"] * p["bm"] * c)
                / peaks["hbm_bytes_per_s"])
    assert read("coded_matmul_roofline.moe") == pytest.approx(
        100 * least / 200e-9)
