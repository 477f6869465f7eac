"""The trace reduction on synthetic traces, and the xplane reader on a
trace recorded on the CPU."""

import pytest

from chipbench import trace as T


def _trace():
    ops = [("fusion.1", 100, 50), ("fusion.2", 140, 30),   # 100-170
           ("coded_matmul", 200, 100),                    # 200-300
           ("fusion.1", 350, 20),                         # 350-370
           ("fusion.3", 900, 200)]                        # 900-1100
    mods = [("jit__sim_batch_jit(1)", 100, 70), ("jit_run(2)", 200, 170)]
    spans = [("window", 50, 950), ("batch", 60, 300), ("recover", 370, 500)]
    dev = {T.OPS_LINE: ops, T.MODULES_LINE: mods}
    return T.Trace({0: dev, 1: {T.OPS_LINE: [("fusion.9", 50, 950)]}}, spans)


def test_busy_and_idle_within_the_window():
    tr = _trace()
    w0, w1 = T.window(tr)
    assert (w0, w1) == (50, 1000)
    ops = tr.devices[0][T.OPS_LINE]
    assert T.busy_intervals(ops, w0, w1) == [(100, 170), (200, 300),
                                             (350, 370), (900, 1000)]
    assert T.busy_ns(ops, w0, w1) == 70 + 100 + 20 + 100
    assert T.idle_gaps(ops, w0, w1) == [(50, 100), (170, 200), (300, 350),
                                        (370, 900)]
    red = T.reduce(tr, [0, 1])
    assert red.window_s == pytest.approx(950e-9)
    assert red.busy_s == {0: pytest.approx(290e-9), 1: pytest.approx(950e-9)}


def test_time_per_module_and_kernel():
    red = T.reduce(_trace(), [0])
    assert red.module("_sim_batch_jit", 0) == (1, 70)
    assert red.ops("coded_matmul", 0) == (1, 100)
    assert red.ops("fusion.1", 0) == (2, 70)
    assert red.ops("absent", 0) == (0, 0)


def test_gaps_are_labelled_by_the_innermost_host_span():
    tr = _trace()
    assert T.label_gap(tr.spans, 170, 200) == "batch"
    assert T.label_gap(tr.spans, 370, 900) == "recover"
    assert T.label_gap(tr.spans, 50, 100) == "batch"
    assert T.label_gap([("window", 0, 10)], 2, 4) == "host"


def test_breakdown_lists_ops_and_longest_gaps():
    bd = T.breakdown(_trace(), 50, 1000, top=3)
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == "fusion.9" and "coded_matmul" in names
    assert bd["device_ops"][0][1] == pytest.approx(950e-9)
    assert bd["idle_gaps"][0] == ["recover", pytest.approx(530e-9)]
    assert len(bd["idle_gaps"]) == 3


def test_missing_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce(_trace(), [0, 2])


def test_reader_finds_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.batch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.read(T.find_xplane(str(tmp_path)))
    names = [s[0] for s in tr.spans]
    assert "window" in names and "batch" in names
    w0, w1 = T.window(tr)
    (b0, bd), = [(s, d) for n, s, d in tr.spans if n == "batch"]
    assert w0 <= b0 and b0 + bd <= w1


def test_peaks_are_keyed_by_device_kind():
    from chipbench import peaks

    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_short_names_of_tpu_operations():
    assert T.short_name("%coded_matmul.1 = bf16[8]{0} custom-call(s32[8] "
                        "%c), custom_call_target=\"tpu_custom_call\"") \
        == "coded_matmul.1"
    assert T.short_name("jit__sim_batch_jit(123)") == "jit__sim_batch_jit(123)"
