"""End-to-end tests of the paper's object: distributed coded matmul with
shard losses + recovery, on one device and (``multidevice``) over an
8-device host mesh in a subprocess."""

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coded_matmul as cm
from repro.core import fountain
from repro.utils import tracing


def _dense_plan():
    # R=64 source blocks, K=16 parities over 8 shards: no single-shard
    # loss peels, so every recovery takes the dense path.
    return cm.plan_coded_matmul(rows=64 * 8, n_shards=8, overhead=0.25, bm=8)


def _peel_plan():
    # R=16, K=8 over 8 shards: every single-shard loss peels.
    return cm.plan_coded_matmul(rows=512, n_shards=8, overhead=0.25, bm=32)


PLANS = {"dense": _dense_plan, "peel": _peel_plan}


def _product(plan, cols=16, seed=0):
    """f32 (a, x) of the plan's rows and the float64 product a @ x."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((plan.code.R * plan.bm, 24)).astype(np.float32)
    x = rng.standard_normal((24, cols)).astype(np.float32)
    return a, x, a.astype(np.float64) @ x.astype(np.float64)


def _survivors(plan, lost):
    return np.setdiff1d(np.arange(plan.n_shards), [lost])


def test_plan_uniform_placement():
    plan = cm.plan_coded_matmul(rows=1024, n_shards=8, overhead=0.25, bm=128)
    assert plan.code.R == 8
    assert plan.placement.shape[0] == 8
    # uniform blocks per shard, disjoint coverage of the coded space
    flat = np.sort(plan.placement.reshape(-1))
    np.testing.assert_array_equal(flat, np.arange(plan.code.n_coded))


def test_run_and_recover_no_loss():
    plan = cm.plan_coded_matmul(rows=64, n_shards=4, overhead=0.5, bm=8)
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    out = cm.run(plan, a, x)
    y = cm.recover(plan, out, survivors=np.arange(4))
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ x), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("lost_shard", [0, 1, 3])
def test_recover_with_lost_shard(lost_shard):
    """The paper's headline property: task completes with any shard down."""
    plan = cm.plan_coded_matmul(rows=64, n_shards=4, overhead=0.6, bm=8, seed=2)
    a = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 8))
    out = cm.run(plan, a, x)
    survivors = np.setdiff1d(np.arange(4), [lost_shard])
    y = cm.recover(plan, out, survivors)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ x), rtol=2e-3, atol=2e-3)


def test_round_robin_spreads_systematic_blocks():
    """Losing one shard must not lose a contiguous run of source blocks."""
    plan = cm.plan_coded_matmul(rows=1024, n_shards=8, overhead=0.25, bm=128)
    sys_blocks_lost = [b for b in plan.placement[0] if b < plan.code.R]
    diffs = np.diff(sys_blocks_lost)
    assert np.all(diffs >= plan.n_shards)


def test_pallas_kernel_path_matches():
    plan = cm.plan_coded_matmul(rows=64, n_shards=4, overhead=0.5, bm=8, seed=1)
    a = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    out_ref = cm.run(plan, a, x, use_pallas=False)
    out_k = cm.run(plan, a, x, use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("lost", range(8))
@pytest.mark.parametrize("path", ["dense", "peel"])
def test_recover_every_single_shard_loss_against_float64(path, lost):
    plan = PLANS[path]()
    a, x, ref = _product(plan)
    out = cm.run(plan, jnp.asarray(a), jnp.asarray(x))
    y = cm.recover(plan, out, _survivors(plan, lost))
    assert y.shape == ref.shape and y.dtype == out.dtype
    np.testing.assert_allclose(np.asarray(y, np.float64), ref,
                               atol=1e-4 * np.abs(ref).max(), rtol=0)
    assert [d[1] for d in plan.decoders.values()] == [path]


@pytest.mark.parametrize("path", ["dense", "peel"])
def test_lost_shard_garbage_never_reaches_the_result(path):
    """The lost shard's rows are selected away, not multiplied by zero:
    NaN there (0 * NaN is NaN) leaves the result finite and unchanged."""
    plan = PLANS[path]()
    a, x, _ = _product(plan, seed=1)
    out = cm.run(plan, jnp.asarray(a), jnp.asarray(x))
    lost, rows = 3, plan.blocks_per_shard * plan.bm
    bad = out.at[lost * rows:(lost + 1) * rows].set(jnp.nan)
    surv = _survivors(plan, lost)
    y, y_bad = cm.recover(plan, out, surv), cm.recover(plan, bad, surv)
    assert np.isfinite(np.asarray(y_bad)).all()
    np.testing.assert_array_equal(np.asarray(y_bad), np.asarray(y))


@pytest.mark.parametrize("path", ["dense", "peel"])
def test_each_survivor_set_builds_once_and_one_program_serves_all(path):
    plan = PLANS[path]()
    a, x, ref = _product(plan, cols=40)   # a width no other test compiles
    out = cm.run(plan, jnp.asarray(a), jnp.asarray(x))
    builds = tracing.totals().get("recover.matrix_build", 0)
    programs = fountain.apply_decoder._cache_size()
    for _ in range(3):
        for lost in range(plan.n_shards):
            y = cm.recover(plan, out, _survivors(plan, lost))
    np.testing.assert_allclose(np.asarray(y, np.float64), ref,
                               atol=1e-4 * np.abs(ref).max(), rtol=0)
    assert tracing.totals()["recover.matrix_build"] - builds == plan.n_shards
    assert len(plan.decoders) == plan.n_shards
    assert fountain.apply_decoder._cache_size() - programs == 1


MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import coded_matmul as cm
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=1, model=8)
    plans = {
        "dense": cm.plan_coded_matmul(rows=64 * 8, n_shards=8,
                                      overhead=0.25, bm=8),
        "peel": cm.plan_coded_matmul(rows=512, n_shards=8, overhead=0.25,
                                     bm=32),
    }
    for path, plan in plans.items():
        rng = np.random.default_rng(0)
        a = rng.standard_normal((plan.code.R * plan.bm, 24)).astype(np.float32)
        x = rng.standard_normal((24, 16)).astype(np.float32)
        ref = a.astype(np.float64) @ x.astype(np.float64)
        out = cm.run(plan, jnp.asarray(a), jnp.asarray(x), mesh=mesh)
        assert out.sharding.spec == jax.sharding.PartitionSpec("model")
        rows = plan.blocks_per_shard * plan.bm
        for lost in range(8):
            surv = np.setdiff1d(np.arange(8), [lost])
            y = cm.recover(plan, out, surv)
            # y is replicated over the mesh, as the survivors' gather was.
            assert y.sharding.is_fully_replicated, y.sharding
            assert y.sharding.mesh.shape == mesh.shape
            err = np.abs(np.asarray(y, np.float64) - ref).max()
            assert err <= 1e-4 * np.abs(ref).max(), (path, lost, err)
            bad = out.at[lost * rows:(lost + 1) * rows].set(jnp.nan)
            np.testing.assert_array_equal(
                np.asarray(cm.recover(plan, bad, surv)), np.asarray(y))
        assert {d[1] for d in plan.decoders.values()} == {path}
    print("MESH-OK")
    """
)


@pytest.mark.multidevice
def test_recover_over_an_8_device_mesh_against_float64():
    """Every single-shard loss, dense and peel plans, the coded output
    sharded over 8 host devices: recovered against float64, replicated,
    and blind to NaN in the lost shard's rows."""
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=str(pathlib.Path(__file__).parent.parent), timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH-OK" in proc.stdout
