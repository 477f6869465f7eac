#!/usr/bin/env python3
"""Compile each cell's programs at their real sizes for a described TPU v5e.

No chip is needed: the TPU compiler in jaxlib compiles for a topology that
is described and not attached.  What it refuses here (a kernel that does not
tile, a program that does not fit) would be refused on the chip.  The
temporaries that ``memory_analysis()`` reports fix the simulator's rep batch
B: the largest power of two whose temporaries stay under half of the chip's
16 GB.

  JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/rehearse.py [--batches 1024,2048,4096]

Prints one JSON line per program.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def sim_batch(topo, batches, config_name="fig3_sc1", traffic_name="ccp_static"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench.generators import sim
    from repro.core import engine, policies, simulator

    config = load_json(HERE / "configs" / f"{config_name}.json")
    traffic = load_json(HERE / "traffic" / f"{traffic_name}.json")
    cfg = sim.scenario(config, traffic)
    R = int(config["R"])
    policy = policies.get(traffic["policy"])
    M = simulator._horizon_shared(cfg, R)   # the engine's first horizon
    one = SingleDeviceSharding(topo.devices[0])
    for B in batches:
        keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=one)
        t0 = time.perf_counter()
        compiled = engine._sim_batch_jit.lower(
            keys, cfg=cfg, R=R, M=M, policy=policy).compile()
        print(json.dumps(dict(program="sim_batch", config=config_name,
                              traffic=traffic_name, B=B, M=M,
                              compile_s=time.perf_counter() - t0,
                              **_mem(compiled))), flush=True)


def coded_matmul(topo, traffic_name):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from chipbench.generators import cmm
    from repro.core import coded_matmul as cm

    config = load_json(HERE / "configs" / "phi4_ffn.json")
    traffic = load_json(HERE / "traffic" / f"{traffic_name}.json")
    shape = cmm.shapes(config, traffic)
    plan = cm.plan_coded_matmul(rows=shape["rows"],
                                n_shards=int(traffic["n_shards"]),
                                overhead=float(traffic["overhead"]),
                                bm=shape["bm"])
    chips = int(traffic["chips"])
    if chips == 1:
        mesh = None
        sh = SingleDeviceSharding(topo.devices[0])
        a_sh = x_sh = sh
    else:
        mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        a_sh = x_sh = NamedSharding(mesh, P())
    a = jax.ShapeDtypeStruct((shape["rows"], shape["k"]), jnp.bfloat16,
                             sharding=a_sh)
    x = jax.ShapeDtypeStruct((shape["k"], shape["n"]), jnp.bfloat16,
                             sharding=x_sh)
    fn = jax.jit(functools.partial(cm.run, plan, mesh=mesh, use_pallas=True,
                                   interpret=False))
    t0 = time.perf_counter()
    compiled = fn.lower(a, x).compile()
    text = compiled.as_text()
    print(json.dumps(dict(program="coded_matmul", traffic=traffic_name,
                          chips=chips, R=plan.code.R, K=plan.code.K,
                          blocks_per_shard=plan.blocks_per_shard,
                          d_max=plan.code.d_max,
                          tpu_custom_call="tpu_custom_call" in text,
                          collectives=[c for c in ("all-gather", "all-reduce",
                                                   "collective-permute")
                                       if c in text],
                          compile_s=time.perf_counter() - t0,
                          **_mem(compiled))), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batches", default="1024,2048,4096")
    ap.add_argument("--only", default="sim,cmm")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    only = args.only.split(",")
    if "sim" in only:
        sim_batch(topo, [int(b) for b in args.batches.split(",")])
    if "cmm" in only:
        for t in ("lost_shard", "lost_chip4"):
            coded_matmul(topo, t)


if __name__ == "__main__":
    main()
