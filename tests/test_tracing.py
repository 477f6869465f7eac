"""The program's flight recorder (``repro.utils.tracing``), its spans in the
collector's recovery, its counter in the engine's horizon loop, and the
simulator's named scopes in the compiled batch program."""

import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coded_matmul as cm
from repro.core import engine, simulator
from repro.core.fleet import FleetConfig
from repro.core.policies import get as get_policy
from repro.utils import tracing


def _spans(recs):
    return sorted((r for r in recs if isinstance(r, tracing.Span)),
                  key=lambda r: r.t0)


def test_nested_spans_carry_parent_and_root_in_each_thread():
    rec = tracing.Recorder()
    seen = {}

    def worker():
        with rec.span("t.root") as attrs:
            attrs["k"] = 1
            with rec.span("t.child"):
                pass

    with rec.span("m.root", n=3):
        with rec.span("m.child"):
            # A thread started inside a span opens requests of its own.
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
            with rec.span("m.grandchild"):
                pass
    assert not th.is_alive()
    for r in _spans(rec.records()):
        seen[r.name] = r
    m, mc, mg = seen["m.root"], seen["m.child"], seen["m.grandchild"]
    assert (m.parent, m.root, m.attrs) == (None, m.id, {"n": 3})
    assert (mc.parent, mc.root) == (m.id, m.id)
    assert (mg.parent, mg.root) == (mc.id, m.id)
    t, tc = seen["t.root"], seen["t.child"]
    assert (t.parent, t.root, t.attrs) == (None, t.id, {"k": 1})
    assert (tc.parent, tc.root) == (t.id, t.id)
    assert len({m.id, mc.id, mg.id, t.id, tc.id}) == 5
    assert m.t0 <= mc.t0 <= mg.t0 <= mg.t1 <= mc.t1 <= m.t1


def test_counters_keep_point_events_and_totals():
    rec = tracing.Recorder()
    rec.count("a")
    with rec.span("req"):
        rec.count("a", 3)
        rec.count("b", 2)
    counts = [r for r in rec.records() if isinstance(r, tracing.Count)]
    assert [(c.name, c.n) for c in counts] == [("a", 1), ("a", 3), ("b", 2)]
    (req,) = _spans(rec.records())
    assert [c.root for c in counts] == [None, req.id, req.id]
    assert req.t0 <= counts[1].t <= req.t1
    assert rec.totals() == {"a": 4, "b": 2}
    # records(t0, t1) keeps what lies inside the interval.
    inside = rec.records(req.t0, req.t1)
    assert {r.name for r in inside} == {"req", "a", "b"}


def test_the_buffer_is_bounded_and_totals_are_not():
    rec = tracing.Recorder(maxlen=5)
    for i in range(12):
        rec.count("x", i)
    recs = rec.records()
    assert len(recs) == 5
    assert [r.n for r in recs] == [7, 8, 9, 10, 11]
    assert rec.totals() == {"x": sum(range(12))}


def test_disable_records_nothing():
    before = len(tracing.records())
    totals = tracing.totals()
    tracing.disable()
    try:
        with tracing.span("off") as attrs:
            attrs["x"] = 1
        with tracing.span("off2") as attrs2:
            pass
        tracing.count("off")
        assert tracing.span("a") is tracing.span("b")
        assert attrs2 == {}
    finally:
        tracing.enable()
    assert len(tracing.records()) == before
    assert tracing.totals() == totals


def _dense_plan():
    # R=64 source blocks, K=16 parities over 8 shards: no single-shard
    # loss peels (the one-chip cell's code at a small block size).
    return cm.plan_coded_matmul(rows=64 * 8, n_shards=8, overhead=0.25, bm=8)


def _peel_plan():
    return cm.plan_coded_matmul(rows=512, n_shards=8, overhead=0.25, bm=32)


def test_coded_matmul_counts_grid_steps_and_real_pairs():
    """One count of each per trace of the op: every (block, slot) step of
    the grid, and the plan's real (block, neighbour) pairs."""
    plan = _dense_plan()
    C, d_max = plan.code.idx.shape
    rows = plan.code.R * plan.bm
    a = jnp.ones((rows, 24), jnp.float32)
    x = jnp.ones((24, 40), jnp.float32)
    before = tracing.totals()
    for _ in range(2):
        cm.run(plan, a, x, use_pallas=True, interpret=True)
    after = tracing.totals()

    def added(name):
        return after.get(name, 0) - before.get(name, 0)

    assert added("coded_matmul.grid_steps") == C * d_max
    assert added("coded_matmul.real_pairs") == np.count_nonzero(
        plan.code.weights)
    assert 0 < np.count_nonzero(plan.code.weights) < C * d_max


PLAN_SPANS = {"dense": ["decode.peel_plan", "decode.rank", "decode.pinv"],
              "peel": ["decode.peel_plan"]}
PROGRAM_SPAN = {"dense": "decode.solve", "peel": "decode.apply"}


@pytest.mark.parametrize("cached", [False, True], ids=["miss", "hit"])
@pytest.mark.parametrize("make_plan,path", [(_dense_plan, "dense"),
                                            (_peel_plan, "peel")])
def test_recover_spans_and_counter(make_plan, path, cached):
    """A recovery's first call for a survivor set builds the matrix (the
    plan spans and ``recover.matrix_build``) and runs the program; a later
    one runs the program only."""
    warm = make_plan()
    rows = warm.code.R * warm.bm
    a = jax.random.normal(jax.random.PRNGKey(0), (rows, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 4), jnp.float32)
    out = cm.run(warm, a, x)
    surv = np.arange(1, warm.n_shards)
    cm.recover(warm, out, surv)                  # compile outside the look
    plan = warm if cached else make_plan()
    t0 = time.perf_counter()
    totals = tracing.totals()
    y = cm.recover(plan, out, surv)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ x), rtol=2e-3,
                               atol=2e-3)
    recs = tracing.records(t0)
    roots = [r for r in _spans(recs) if r.name == "recover"]
    assert len(roots) == 1
    root = roots[0]
    assert root.parent is None and root.root == root.id
    assert root.attrs == {"n_rx": 7 * plan.blocks_per_shard, "path": path}
    kids = [r for r in _spans(recs) if r.id != root.id]
    children = ([] if cached else PLAN_SPANS[path]) + [PROGRAM_SPAN[path]]
    assert [k.name for k in kids] == children
    assert all(k.parent == root.id and k.root == root.id for k in kids)
    assert all(root.t0 <= k.t0 <= k.t1 <= root.t1 for k in kids)
    after = tracing.totals()
    counter = f"decode.{path}"
    assert after.get(counter, 0) - totals.get(counter, 0) == 1
    other = "decode.peel" if path == "dense" else "decode.dense"
    assert after.get(other, 0) == totals.get(other, 0)
    counts = ([] if cached else ["recover.matrix_build"]) + [counter]
    evs = [r for r in recs if isinstance(r, tracing.Count)]
    assert [ev.name for ev in evs] == counts
    assert all(ev.root == root.id for ev in evs)


@pytest.mark.parametrize("cached", [False, True], ids=["miss", "hit"])
def test_profiler_trace_holds_the_recover_span(tmp_path, cached):
    warm = _dense_plan()
    a = jnp.ones((warm.code.R * warm.bm, 8), jnp.float32)
    out = cm.run(warm, a, jnp.ones((8, 2), jnp.float32))
    surv = np.arange(1, warm.n_shards)
    cm.recover(warm, out, surv)
    plan = warm if cached else _dense_plan()
    jax.profiler.start_trace(str(tmp_path))
    try:
        cm.recover(plan, out, surv).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tracing.PREFIX):
                        found[ev.name] = dict(ev.stats)
    assert found["c3p.recover"].get("n_rx") == 7 * plan.blocks_per_shard
    spans = ([] if cached else PLAN_SPANS["dense"]) + ["decode.solve"]
    assert set(found) == {"c3p.recover"} | {tracing.PREFIX + s for s in spans}


def test_horizon_reruns_are_counted(monkeypatch):
    cfg = simulator.ScenarioConfig(N=5)
    start = 16
    monkeypatch.setattr(engine, "_initial_m", lambda *a, **k: start)
    before = tracing.totals().get("engine.horizon_rerun", 0)
    res = engine.Engine().run(cfg, "ccp", simulator.batch_keys(2), 60)
    reruns = tracing.totals().get("engine.horizon_rerun", 0) - before
    assert res.M > start
    assert reruns == int(np.log2(res.M // start))


_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.mark.parametrize("fleet", [False, True])
def test_batch_program_carries_the_simulator_scopes(fleet):
    cfg = simulator.ScenarioConfig(N=6)
    keys = simulator.batch_keys(2)
    policy = get_policy("ccp")
    if fleet:
        lowered = engine._fleet_batch_jit.lower(keys, cfg, 50, 32, policy,
                                                FleetConfig())
    else:
        lowered = engine._sim_batch_jit.lower(keys, cfg, 50, 32, policy)
    names = _OP_NAME.findall(lowered.compile().as_text())
    parts = {re.sub(r"^(?:\w+\()+|\)+$", "", p)
             for n in names for p in n.split("/")}
    assert {"draws", "policy_stream", "completion"} <= parts
    # The completion sort and the pacing scan's loop sit under their scopes.
    assert any("completion" in n and "sort" in n for n in names)
    assert any("policy_stream" in n and n.endswith("while") for n in names)


def test_coded_expert_layer_scopes_spans_and_counters():
    """The layer's programs carry the ``moe.*`` scopes in their compiled
    op_name metadata; each request's two ``recover`` spans name their
    product; the kernel's counters count once per trace of a program."""
    from repro.core import coded_moe as CM

    d, f, E = 128, 64, 16
    share = CM.ExpertShare(d_model=d, d_expert=f, n_experts=E, top_k=4,
                           n_shared=2, share=0, n_shares=4,
                           scoring="sigmoid", scale=2.446)
    layer = CM.CodedExperts(share, n_shards=4, overhead=0.5, bm_gate_up=16,
                            bm_down=16, use_pallas=True,
                            interpret=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    w_gu, w_down = CM.pack(
        jax.random.normal(ks[0], (4, f, d)), jax.random.normal(ks[1], (4, f, d)),
        jax.random.normal(ks[2], (4, d, f)),
        jax.random.normal(ks[3], (2 * f, d)),
        jax.random.normal(ks[4], (2 * f, d)),
        jax.random.normal(ks[5], (d, 2 * f)))
    params = dict(router=jax.random.normal(ks[0], (E, d)),
                  bias=jnp.zeros((E,)), w_gu=w_gu, w_down=w_down)
    x = jax.random.normal(ks[1], (d, 64))
    before = tracing.totals().get("coded_matmul.grid_steps", 0)
    layer(params, x, 1)
    first = tracing.totals().get("coded_matmul.grid_steps", 0) - before
    cols = layer(params, x, 2)[3]
    t0 = time.perf_counter()
    layer(params, x, 3)
    again = tracing.totals().get("coded_matmul.grid_steps", 0) - before
    tiles = cols.tile_group.shape[0]
    assert first == again == tiles * sum(
        p.code.idx.size for p in (layer.plan_gu, layer.plan_dn))
    recs = _spans(tracing.records(t0))
    recovers = [r for r in recs if r.name == "recover"]
    assert [r.attrs["product"] for r in recovers] == ["gate_up", "down"]
    assert [r.name for r in recs if r.name == "moe.size"] == ["moe.size"]
    found = set()
    for texts in layer.programs().values():
        for text in texts:
            for name in _OP_NAME.findall(text):
                found.update(p for p in name.split("/") if p.startswith("moe."))
    assert found == {"moe.route", "moe.swiglu", "moe.combine"}
