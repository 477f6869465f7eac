"""Serving launcher: batched generate with optional CCP dispatch replicas.

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --requests 16 --batch 4 --prompt-len 16 --new-tokens 8 --replicas 2

Full width on one chip needs bf16 params and compute (``--dtype bfloat16``):
phi4-mini-3.8b holds 7.7 GB of weights in bf16, twice that in f32.
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slow-factor", type=float, default=0.0,
                    help="artificial delay (s) on odd replicas — demo of CCP "
                         "dispatch over heterogeneous replicas")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="param and compute dtype")
    args = ap.parse_args()

    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.serve_loop import CCPDispatcher, ServeEngine, init_params
    from repro.utils import compile_cache

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke, param_dtype=args.dtype,
                     compute_dtype=args.dtype)
    model = build_model(cfg)
    params = init_params(model, args.seed)
    engine = ServeEngine(model, params, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    batches = [
        rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)).astype(np.int32)
        for _ in range(args.requests)
    ]

    def make_replica(i):
        def run(b):
            if args.slow_factor and i % 2 == 1:
                time.sleep(args.slow_factor)
            return engine.generate(b, n_new=args.new_tokens)
        return run

    t0 = time.time()
    if args.replicas > 1:
        disp = CCPDispatcher([make_replica(i) for i in range(args.replicas)])
        results, allocs = disp.run(batches)
        print(f"dispatch allocations: first={allocs[0].tolist()} "
              f"last={allocs[-1].tolist()}")
    else:
        results = [make_replica(0)(b) for b in batches]
    dt = time.time() - t0
    toks = sum(r.shape[0] * r.shape[1] for r in results)
    print(f"served {len(results)} request batches / {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
