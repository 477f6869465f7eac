"""The readers of the program's spans, counters and named scopes, on
synthetic traces, and the cmm and sim readers on a context made from a
small run on the CPU."""

import gc
import importlib

import pytest

from chipbench import program_trace as pt
from chipbench import run as R
from chipbench import trace as T
from chipbench.spans import Spans
from chipbench.tests.faults import SMALL


def test_anchor_offset_pairs_spans_by_name_and_order():
    off = 5_000_000_000.0
    host = [("window", 1.0, 9.0), ("recover", 2.0, 2.5), ("recover", 3.0, 3.5),
            ("coded_matmul", 1.5, 1.9)]
    traced = [("window", int(1.0e9 + off), 8_000_000_000),
              ("recover", int(2.0e9 + off) + 40, 500_000_000),
              ("recover", int(3.0e9 + off) - 40, 500_000_000),
              # two in the trace, one on the host: left out
              ("coded_matmul", int(1.5e9 + off), 1),
              ("coded_matmul", int(1.6e9 + off), 1)]
    offs = pt.anchor_offsets(host, traced)
    assert len(offs) == 3
    assert max(offs) - min(offs) == pytest.approx(80, abs=1e-3)
    assert sorted(offs)[1] == pytest.approx(off, abs=1e-3)


HLO = """\
HloModule jit__sim_batch_jit, entry_computation_layout={(u32[4,2]{1,0})->f32[4]{0}}

%body.1 (p: (s32[], f32[4,10])) -> (s32[], f32[4,10]) {
  %fusion.427 = f32[4,10]{1,0} fusion(%x), kind=kLoop, calls=%fc.1, metadata={op_type="select_n" op_name="jit(_sim_batch_jit)/vmap(policy_stream)/jit(policy_stream)/while/body/select_n" source_file="x.py" source_line=3}
  ROOT %tuple.3 = (s32[], f32[4,10]) tuple(%a, %fusion.427)
}

ENTRY %main.9 (Arg_0.1: u32[4,2]) -> f32[4] {
  %while.154 = (s32[], f32[4,512]) while(%t), condition=%c, body=%b, metadata={op_type="while" op_name="jit(_sim_batch_jit)/vmap(draws)/jit(_poisson)/jit(_poisson_rejection)/while"}
  %while.201 = (s32[], f32[4,10]) while(%t2), condition=%c2, body=%body.1, metadata={op_name="jit(_sim_batch_jit)/vmap(policy_stream)/jit(policy_stream)/while"}
  %sort.5 = f32[4,51200]{1,0} sort(%y), dimensions={1}, to_apply=%cmp, metadata={op_type="sort" op_name="jit(_sim_batch_jit)/vmap(completion)/sort"}
  %draw_in_stream = f32[4] add(%p, %q), metadata={op_name="jit(_sim_batch_jit)/vmap(policy_stream)/draws/add"}
  %copy.3 = f32[4] copy(%z)
  ROOT %tuple.9 = (f32[4]) tuple(%copy.3)
}
"""


def test_scope_map_reads_op_name_metadata():
    m = pt.scope_map(HLO)
    assert m == {"fusion.427": "policy_stream", "while.154": "draws",
                 "while.201": "policy_stream", "sort.5": "completion",
                 "draw_in_stream": "draws"}
    assert pt.scope_of("jit(f)/jit(main)/vmap(jit(draws))/x") == "draws"
    assert pt.scope_of("jit(f)/withdraws/x") is None


def test_union_and_outermost_count_nested_operations_once():
    assert pt.union_ns([(0, 10), (5, 20), (30, 40), (35, 38)]) == 30
    ops = [("while.201", 100, 100), ("fusion.427", 110, 50),
           ("fusion.428", 170, 20), ("sort.5", 250, 30), ("copy.3", 290, 5)]
    tops = pt.outermost(ops)
    assert [(n, a, b) for n, a, b, _ in tops] == [
        ("while.201", 100, 200), ("sort.5", 250, 280), ("copy.3", 290, 295)]
    assert [n for n, _, _ in tops[0][3]] == ["fusion.427", "fusion.428"]
    smap = {"while.201": "policy_stream", "sort.5": "completion"}
    mods = [("jit__sim_batch_jit(1)", 90, 210)]
    # An operation outside the module (another program) is left out.
    by_scope, per_op = pt.split_by_scope(ops + [("fusion.1", 400, 10)], mods,
                                         smap, 0, 1000)
    assert by_scope == {"policy_stream": 100, "completion": 30, None: 5}
    assert per_op["policy_stream"] == {"while.201": 100, "fusion.427": 50,
                                       "fusion.428": 20}
    # Clipped to the window.
    by_scope, _ = pt.split_by_scope(ops, mods, smap, 150, 260)
    assert by_scope == {"policy_stream": 50, "completion": 10}


def test_idle_goes_to_the_innermost_program_span():
    spans = [(pt.ROOT_SELF, 100, 200, 0), ("decode.pinv", 120, 150, 1),
             ("decode.solve", 150, 190, 1),
             (pt.ROOT_SELF, 500, 600, 0), ("recover.gather", 500, 550, 1)]
    gaps = [(50, 130), (140, 210), (520, 580), (700, 720)]
    out = pt.idle_by_span(gaps, spans)
    assert out == {pt.NO_SPAN: 50 + 10 + 20, pt.ROOT_SELF: 20 + 10 + 30,
                   "decode.pinv": 10 + 10, "decode.solve": 40,
                   "recover.gather": 30}
    assert sum(out.values()) == sum(b - a for a, b in gaps)


def _cmm_ctx():
    """A context as ``run.run`` makes it, from a small window of the
    one-chip cmm cell on the CPU, with a trace made to match: the harness
    spans on a clock 7 s ahead, and the device busy except inside
    ``recover``'s ``decode.pinv`` spans."""
    workload = "cmm.phi4_ffn.lost_shard"
    spec = R.resolve(R.load_benchmark(), workload)
    small = SMALL["cmm"]
    config = dict(spec["config"], **small["config"])
    # 16-row blocks make R=64 source blocks, as in the cell: every
    # single-shard loss recovers by the dense solve.
    traffic = dict(spec["traffic"], **dict(small["traffic"], block_rows=16))
    gen = importlib.import_module("chipbench.generators.cmm")
    spans = Spans()
    wl = gen.Workload(config, traffic, spec["limits"], 2 ** 33 + 1, 1, spans)
    wl.warm()
    with spans.span("window"):
        res = wl.window(0.5)
    wl.release()
    gc.collect()
    off = 7e9
    traced = [(n, int(a * 1e9 + off), int((b - a) * 1e9))
              for n, a, b in spans.records]
    from repro.utils import tracing
    (_, w0, w1), = [r for r in spans.records if r[0] == "window"]
    pinv = [(int(r.t0 * 1e9 + off), int(r.t1 * 1e9 + off))
            for r in tracing.records(w0, w1)
            if isinstance(r, tracing.Span) and r.name == "decode.pinv"]
    ops, t = [], int(w0 * 1e9 + off)
    for a, b in pinv:
        ops.append(("fusion", t, a - t))
        t = b
    ops.append(("fusion", t, int(w1 * 1e9 + off) - t))
    trace = T.Trace({0: {T.OPS_LINE: ops, T.MODULES_LINE: []}}, traced)
    ctx = R.Context(workload=workload, trace=T.reduce(trace, [0]),
                    spans=spans, result=res, work=res["work"], devices=[0],
                    chips=1, peaks=None, compiles_in_window=0)
    return ctx, pinv


def test_cmm_readers_on_a_small_cpu_run():
    ctx, pinv = _cmm_ctx()
    n = ctx.result["attempted"]
    assert n >= 1 and len(pinv) == n
    assert R.load_reader("pinv_per_product.cmm")(ctx) == 1.0
    plan = R.load_reader("recover_plan_ms.cmm")(ctx)
    dispatch = R.load_reader("recover_dispatch_ms.cmm")(ctx)
    host = R.load_reader("recover_ms.cmm")(ctx)
    assert 0 < plan < host and 0 < dispatch < host
    assert plan + dispatch <= host
    idle = R.load_reader("recover_idle_ms.cmm")(ctx)
    pinv_ms = sum(b - a for a, b in pinv) / 1e6 / n
    assert idle == pytest.approx(pinv_ms, rel=1e-6)


def test_readers_report_nothing_without_program_records():
    class Ctx:
        spans = Spans()
        result = {"attempted": 3}

        def log(self, *a):
            pass

    ctx = Ctx()
    ctx.spans.records.append(("window", 0.0, 1.0))
    # Nothing the program recorded lies in a window so early.
    for name in ("recover_plan_ms.cmm", "recover_dispatch_ms.cmm",
                 "recover_idle_ms.cmm"):
        assert R.load_reader(name)(ctx) is None
    assert R.load_reader("pinv_per_product.cmm")(ctx) == 0.0
    assert R.load_reader("horizon_reruns.sim")(ctx) == 0.0


def test_sim_scope_map_of_the_cell_program():
    class Ctx:
        workload = "sim.fig3_sc1.ccp_static"
        work = {"horizons": [16]}

        def log(self, *a):
            pass

    smap = pt.sim_scope_map(Ctx())
    assert smap is not None
    assert set(smap.values()) == set(pt.SIM_SCOPES)
    assert any(k.startswith("while") and v == "policy_stream"
               for k, v in smap.items())
    assert any(k.startswith("sort") and v == "completion"
               for k, v in smap.items())


def test_sim_scope_map_past_a_cached_program_without_scopes(tmp_path,
                                                             monkeypatch):
    """The persistent cache's key leaves op_name metadata out, so a cache
    shared with an older checkout serves its executable, which has no
    scopes, to this one; the reader still finds every scope."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chipbench.generators.sim import scenario
    from repro.core import engine, policies

    class Ctx:
        workload = "sim.fig3_sc1.ccp_static"
        work = {"horizons": [16]}

        def log(self, *a):
            pass

    spec = R.resolve(R.load_benchmark(), Ctx.workload)
    keys = jax.ShapeDtypeStruct(
        (int(spec["traffic"]["reps_per_batch"]), 2), jax.numpy.uint32)
    args = (keys, scenario(spec["config"], spec["traffic"]),
            int(spec["config"]["R"]), 16,
            policies.get(spec["traffic"]["policy"]))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        jax.clear_caches()
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            old = engine._sim_batch_jit.lower(*args).compile().as_text()
        assert set(pt.scope_map(old).values()) == {"policy_stream"}
        jax.clear_caches()
        # What the program runs now comes from that cache entry.
        served = engine._sim_batch_jit.lower(*args).compile().as_text()
        assert "draws" not in set(pt.scope_map(served).values())
        smap = pt.sim_scope_map(Ctx())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
    assert set(smap.values()) == set(pt.SIM_SCOPES)
    # The instruction names are those of the program that ran.
    assert set(smap) <= {m.group(1) for m in map(pt._INSTR.match,
                                                 served.splitlines()) if m}


def test_sim_readers_report_nothing_for_a_program_without_scopes(
        monkeypatch):
    import contextlib

    import jax

    class Ctx:
        workload = "sim.fig3_sc1.ccp_static"
        work = {"horizons": [16]}
        result = {"attempted": 2048}
        logged = []

        def log(self, *a):
            self.logged.append(" ".join(a))

    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    try:
        ctx = Ctx()
        assert pt.sim_scope_map(ctx) is None
        assert R.load_reader("scan_device_ms_per_krep.sim")(ctx) is None
        assert "no scopes" in ctx.logged[0]
    finally:
        jax.clear_caches()
