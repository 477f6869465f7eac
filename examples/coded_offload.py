"""The paper's Fig. 1 end-to-end on a device mesh: fountain-coded y = A x
offloaded across 8 'helper' shards (shard_map over the model axis), with a
straggler killed mid-task, plus the fused Pallas kernel path.

PYTHONPATH=src python examples/coded_offload.py
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import coded_matmul
from repro.launch.mesh import make_host_mesh


def main():
    mesh = make_host_mesh(data=1, model=8)
    plan = coded_matmul.plan_coded_matmul(rows=1024, n_shards=8,
                                          overhead=0.5, bm=32,
                                          validate_losses=2)
    print(f"code: R={plan.code.R} source + K={plan.code.K} parity blocks, "
          f"{plan.blocks_per_shard} blocks/shard, "
          f"validated for any 2-shard loss")

    a = jax.random.normal(jax.random.PRNGKey(0), (1024, 256), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32)

    # distributed compute: every device encodes + multiplies its own blocks
    out = coded_matmul.run(plan, a, x, mesh=mesh, axis="model")
    y_ref = a @ x

    for survivors in (np.arange(8), np.array([0, 1, 2, 4, 5, 6, 7]),
                      np.array([1, 2, 3, 4, 6, 7])):
        y = coded_matmul.recover(plan, out, survivors)
        err = float(jnp.abs(y - y_ref).max())
        lost = sorted(set(range(8)) - set(survivors.tolist()))
        print(f"  lost shards {lost or 'none'}: max|err| = {err:.2e}")

    # fused Pallas kernel path (native on a TPU, interpreted elsewhere)
    out_k = coded_matmul.run(plan, a, x, use_pallas=True,
                             interpret=jax.default_backend() != "tpu")
    err = float(jnp.abs(out_k - coded_matmul.run(plan, a, x)).max())
    print(f"  pallas fused-kernel path max|err| vs jnp: {err:.2e}")


if __name__ == "__main__":
    main()
