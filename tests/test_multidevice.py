"""Multi-device distribution tests on 8 host devices (subprocess-isolated so
the main test session keeps its single-device view).

Covers: GSPMD-sharded train step vs single-device reference, the shard_map
coded matmul mesh path, and the sharded cross-entropy collective helper.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    from repro.configs import get_config
    from repro.core import coded_matmul as cm
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model, cross_entropy
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from repro.parallel.collectives import sharded_cross_entropy
    from repro.runtime.train_loop import make_train_step

    out = {}

    # ---- 1. sharded train step == single-device step ----------------------
    cfg = get_config("gemma2-27b", smoke=True)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, schedule="constant",
                                weight_decay=0.0)
    step = make_train_step(model, opt_cfg, 2, pre_shaped=True)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}

    p1, o1, m1 = jax.jit(step)(params, adamw.init(params), batch)  # 1 device

    mesh = make_host_mesh(data=4, model=2)
    rules = shd.make_rules(cfg, mesh)
    p_sh = shd.param_shardings(mesh, axes, rules)
    params_d = jax.device_put(params, p_sh)
    with mesh:
        p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, None, None),
                             out_shardings=(p_sh, None, None))(
            params_d, adamw.init(params_d), batch)
    err = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
              for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    out["train_step_max_err"] = err
    out["loss_diff"] = abs(float(m1["loss"]) - float(m2["loss"]))

    # ---- 2. coded matmul over a real mesh ---------------------------------
    plan = cm.plan_coded_matmul(rows=256, n_shards=8, overhead=0.5, bm=16)
    a = jax.random.normal(jax.random.PRNGKey(2), (256, 64))
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    mesh8 = make_host_mesh(data=1, model=8)
    o_mesh = cm.run(plan, a, x, mesh=mesh8, axis="model")
    o_ref = cm.run(plan, a, x)
    out["coded_matmul_mesh_err"] = float(jnp.abs(o_mesh - o_ref).max())
    y = cm.recover(plan, o_mesh, survivors=np.array([0, 2, 3, 4, 5, 6, 7]))
    out["coded_matmul_recover_err"] = float(jnp.abs(y - a @ x).max())

    # ---- 3. sharded cross-entropy == dense cross-entropy ------------------
    V, B, T = 64, 2, 8
    logits = jax.random.normal(jax.random.PRNGKey(4), (B, T, V))
    labels = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, V)
    dense = float(cross_entropy(logits, labels))

    mesh_v = make_host_mesh(data=1, model=8)

    def local_ce(lg, lb):
        idx = jax.lax.axis_index("model")
        vstart = idx * (V // 8)
        return sharded_cross_entropy(lg, lb, vstart, "model")

    ce = shard_map(local_ce, mesh=mesh_v,
                   in_specs=(P(None, None, "model"), P()),
                   out_specs=P(), check_vma=False)(logits, labels)
    out["sharded_ce_err"] = abs(float(ce) - dense)
    print("RESULT:" + json.dumps(out))
    """
)


@pytest.mark.slow
def test_multidevice_distribution():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1500,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["train_step_max_err"] < 2e-4, out
    assert out["loss_diff"] < 1e-4, out
    assert out["coded_matmul_mesh_err"] < 1e-4, out
    assert out["coded_matmul_recover_err"] < 5e-3, out
    assert out["sharded_ce_err"] < 1e-5, out
