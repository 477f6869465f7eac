"""Training launcher.

Real (executing) runs on whatever devices exist; the production-mesh path
is exercised by dryrun.py.  Supports the full framework: sharded params,
microbatched/remat step, CCP scheduler telemetry, coded-DP (optional),
async checkpointing, deterministic data.

  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b --smoke \
      --steps 50 --batch 8 --seq 64 --devices 8 --mesh 8,1 --ckpt /tmp/ck
"""

import argparse
import os


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="1,1", help="data,model axis sizes")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (must be set before jax init)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--coded-dp", action="store_true",
                    help="use the coded-DP (R-of-R+K) training step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args()


def main():
    args = _parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        )
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import checkpoint as ck
    from repro.configs import get_config
    from repro.core.scheduler import CCPScheduler
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim import adamw
    from repro.parallel import sharding as shd
    from repro.runtime.train_loop import make_coded_train_step, make_train_step
    from repro.utils import compile_cache

    compile_cache.enable()
    overrides = {}
    for kv in filter(None, os.environ.get("REPRO_TRAIN_OVERRIDES", "").split(",")):
        k, v = kv.split("=")
        overrides[k] = int(v)
    cfg = get_config(args.arch, smoke=args.smoke, **overrides)
    model = build_model(cfg, remat=True)
    data_n, model_n = (int(x) for x in args.mesh.split(","))
    mesh = make_host_mesh(data=data_n, model=model_n)
    rules = shd.make_rules(cfg, mesh)

    params, axes = model.init(jax.random.PRNGKey(args.seed))
    p_sh = shd.param_shardings(mesh, axes, rules)
    params = jax.device_put(params, p_sh)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                                total_steps=args.steps)
    opt_state = adamw.init(params)

    data = SyntheticLM(cfg.vocab, args.seq, args.batch, n_micro=args.n_micro,
                       seed=args.seed)
    start = 0
    ckpt = None
    if args.ckpt:
        ckpt = ck.AsyncCheckpointer(args.ckpt)
        if args.resume and ck.latest_step(args.ckpt) is not None:
            tgt = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"params": params, "opt": opt_state},
            )
            state, meta = ck.restore(args.ckpt, None, tgt,
                                     {"params": p_sh, "opt": None})
            params, opt_state = state["params"], state["opt"]
            start = int(meta.get("step", 0))
            print(f"resumed from step {start}")

    sched = CCPScheduler(n_workers=data_n)
    if args.coded_dp:
        step_fn, code, (pats, ws) = make_coded_train_step(
            model, opt_cfg, mesh, seed=args.seed)
        w0 = jnp.asarray(ws[0])

        def run_step(params, opt_state, batch):
            # batch (n_micro, mb, T) -> coded step wants (R, mb', T)
            tok = batch["tokens"].reshape(data_n, -1, batch["tokens"].shape[-1])
            lab = batch["labels"].reshape(data_n, -1, batch["labels"].shape[-1])
            return step_fn(params, opt_state, {"tokens": tok, "labels": lab}, w0)
    else:
        raw = make_train_step(model, opt_cfg, args.n_micro, pre_shaped=True)
        jit_step = jax.jit(raw, donate_argnums=(0, 1))

        def run_step(params, opt_state, batch):
            return jit_step(params, opt_state, batch)

    t_start = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        t0 = time.time()
        with mesh:
            params, opt_state, metrics = run_step(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        sched.observe_step(np.full(data_n, dt))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save_async(step + 1, {"params": params, "opt": opt_state},
                            metadata={"step": step + 1})
    if ckpt:
        ckpt.wait()
    print(f"done: {args.steps - start} steps in {time.time()-t_start:.1f}s, "
          f"final loss {loss:.4f}")
    return loss


if __name__ == "__main__":
    main()
