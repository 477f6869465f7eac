#!/usr/bin/env python3
"""Smoke run of C3P's main path on a TPU, through the normal entry points.

  python chip_smoke.py            # one chip: three phases, below
  python chip_smoke.py --chips 4  # four chips: only the paths across chips

One chip, one process, three phases:

* ``simulator`` — ``Engine.run`` on the paper's Fig. 3 Scenario-1 pool
  (N=100 helpers, R=8000 packets) for ``ccp`` and ``best`` over 1024 reps,
  certification doubling as normal.  CCP must keep its helpers >= 99% busy
  and finish within 2% of the eq. (27) optimum; a few keys re-run on the
  host CPU must agree with the chip.
* ``coded_matmul`` — the fused Pallas coded matmul (``tpu_custom_call`` in
  the compiled program) at rows=8192 (R=32 blocks of 256), k=n=4096, in f32
  and bf16; one of 8 shards is lost and the product recovered, once by
  peeling and once by the dense solve.  Then the Pallas LT peeling decode
  against its jnp reference.
* ``serve`` — phi4-mini-3.8b at full width in bf16 answers 4 batches of 4
  prompts of 128 tokens with 16 greedy tokens each; greedy decode must be
  deterministic and its cached logits must match an uncached forward.

``--chips 4`` runs the coded matmul sharded over a 4-chip ``model`` mesh
with one chip's shard lost, and ``Engine.run(shard=True)`` over the four
chips against the same keys on one chip.

Each phase prints one JSON line with its shapes, errors, tolerances and
wall seconds split into compile and run.  The last line is
``{"ok": true, "device": {...}}``; a failed check exits non-zero without
it, and so does a machine whose JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), summed from its monitoring events."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


CLOCK = CompileClock()


class Phase:
    """One phase's record: numbers, failed checks, compile/run seconds."""

    def __init__(self, name):
        self.rec = {"phase": name}
        self.failed = []
        self._t0 = time.perf_counter()
        self._c0 = CLOCK.seconds

    def check(self, name, value, tol, ok):
        self.rec[name] = value
        self.rec[name + "_tol"] = tol
        if not ok:
            self.failed.append(f"{name}={value!r} (tol {tol!r})")

    def done(self):
        wall = time.perf_counter() - self._t0
        comp = CLOCK.seconds - self._c0
        self.rec.update(compile_s=comp, run_s=wall - comp,
                        ok=not self.failed)
        if self.failed:
            self.rec["failed"] = self.failed
        print(json.dumps(self.rec), flush=True)
        return self.failed


def _rel_err(y, ref):
    import jax.numpy as jnp

    y, ref = jnp.asarray(y, jnp.float32), jnp.asarray(ref, jnp.float32)
    return float(jnp.abs(y - ref).max() / jnp.abs(ref).max())


# ---------------------------------------------------------------------------
# Phase 1: the Monte-Carlo engine at paper scale
# ---------------------------------------------------------------------------

def phase_simulator(reps=1024, R=8000, n_cpu=8):
    import jax
    import numpy as np

    from repro.configs.ccp_paper import FIG3
    from repro.core import engine, simulator, theory

    ph = Phase("simulator")
    cfg = FIG3[1]
    keys = simulator.batch_keys(reps, SEED)
    eng = engine.Engine()
    res = {p: eng.run(cfg, p, keys, R) for p in ("ccp", "best")}
    ccp = res["ccp"]
    K = cfg.K(R)
    t_opt = np.array([theory.t_opt_model1(R, K, ccp.a[i], ccp.mu[i])
                      for i in range(reps)])
    ph.rec.update(N=cfg.N, R=R, K=K, reps=reps,
                  M={p: r.M for p, r in res.items()},
                  T_mean={p: float(r.T.mean()) for p, r in res.items()},
                  t_opt_mean=float(t_opt.mean()))
    for p, r in res.items():
        ph.check(f"uncertified_{p}", int((~r.valid).sum()), 0,
                 bool(r.valid.all()))
    # Paper claims on this pool: CCP's helpers are >= 99% busy, and its
    # completion time sits on the eq. (27) optimum.  Per rep T/t_opt
    # spreads about +-2% (host CPU, 32 reps); the mean over 1024 reps has a
    # standard error near 0.05%, so a 2% gap is a real departure.
    eff = float(np.nanmean(ccp.efficiency))
    ph.check("ccp_efficiency", eff, 0.99, eff >= 0.99)
    gap = float(ccp.T.mean() / t_opt.mean() - 1.0)
    ph.check("ccp_T_over_t_opt_minus_1", gap, 0.02, abs(gap) <= 0.02)

    # The same keys on the host CPU, at the chip run's horizon.  The key
    # bits agree (threefry is integer arithmetic); the transcendentals of
    # the timing draws differ by a few ulps, so T may differ at f32
    # rounding, and a packet can change hands where two arrivals tie.
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = eng.run(cfg, "ccp", jax.device_put(keys[:n_cpu], cpu), R,
                      M_override=ccp.M)
    dT = float(np.max(np.abs(ref.T - ccp.T[:n_cpu]) / ref.T))
    ph.check("cpu_T_rel_diff", dT, 1e-4, dT <= 1e-4)
    dr = int(np.abs(ref.r_n - ccp.r_n[:n_cpu]).sum(axis=1).max())
    ph.check("cpu_r_n_moved_packets", dr, 2, dr <= 2)
    return ph.done()


# ---------------------------------------------------------------------------
# Phase 2: fused coded matmul + recovery, and the LT peeling decode
# ---------------------------------------------------------------------------

def _coded_matmul_case(ph, tag, plan, a, x, losses, mesh=None):
    """Compile and run ``coded_matmul.run`` with the native kernel, check
    the coded blocks against the generator applied to a @ x, then recover
    a @ x after each lost shard in ``losses``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import coded_matmul, fountain

    fn = jax.jit(functools.partial(coded_matmul.run, plan, mesh=mesh,
                                   use_pallas=True, interpret=False))
    compiled = fn.lower(a, x).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    ph.check(f"{tag}_tpu_custom_call", has_kernel, True, has_kernel)
    out = jax.block_until_ready(compiled(a, x))
    hi = jax.lax.Precision.HIGHEST
    y = jnp.matmul(a.astype(jnp.float32), x.astype(jnp.float32),
                   precision=hi)
    R, bm = plan.code.R, plan.bm
    G = jnp.asarray(plan.code.dense_generator()[plan.placement.reshape(-1)])
    coded = jnp.einsum("cr,rbn->cbn", G, y.reshape(R, bm, -1),
                       precision=hi).reshape(out.shape)
    # f32 end to end: errors of f32 rounding, far under 1e-4 of the scale.
    # bf16: the kernel accumulates in f32 and rounds each output once
    # (u = 2^-8); a recovered block subtracts up to d = 16 rounded parity
    # neighbours from a rounded parity, so first order 2 * d * u.
    bf16 = a.dtype == jnp.bfloat16
    u = 2.0 ** -8
    tol_k, tol_r = (2 * u, 2 * 16 * u) if bf16 else (1e-4, 1e-4)
    e = _rel_err(out, coded)
    ph.check(f"{tag}_kernel_rel_err", e, tol_k, e <= tol_k)
    for lost in losses:
        survivors = np.setdiff1d(np.arange(plan.n_shards), [lost])
        ids = plan.placement[survivors].reshape(-1)
        method = ("peel" if fountain.peel_decode_plan(plan.code, ids)
                  is not None else "dense")
        rec = coded_matmul.recover(plan, out, survivors)
        e = _rel_err(rec, y)
        ph.check(f"{tag}_lost{lost}_{method}_rel_err", e, tol_r, e <= tol_r)
    return out


def phase_coded_matmul(rows=8192, k=4096, n=4096, bm=256):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import coded_matmul, decode, fountain
    from repro.kernels.lt_decode import lt_decode, lt_decode_ref
    from repro.kernels.lt_encode import lt_encode_code

    ph = Phase("coded_matmul")
    # 8 logical shards on one chip; the plan is validated for any 1-shard
    # loss.  Losing shard 3 recovers by peeling, shard 7 by the dense solve.
    plan = coded_matmul.plan_coded_matmul(rows=rows, n_shards=8,
                                          overhead=0.25, bm=bm)
    ph.rec.update(rows=rows, k=k, n=n, bm=bm, R=plan.code.R, K=plan.code.K,
                  shards=plan.n_shards)
    ka, kx = jax.random.split(jax.random.PRNGKey(SEED))
    for dt in (jnp.float32, jnp.bfloat16):
        a = jax.random.normal(ka, (rows, k), jnp.float32).astype(dt)
        x = jax.random.normal(kx, (k, n), jnp.float32).astype(dt)
        _coded_matmul_case(ph, jnp.dtype(dt).name, plan, a, x, (3, 7))

    # LT peeling decode: the decoder code of the engine, 8 of 64 systematic
    # blocks lost, payload encoded and decoded with the native kernels.
    R, K, cols, dbm = 64, 64, 4096, 256
    dcode = decode.make_decoder_code(R, K)
    lost = np.random.default_rng(R).choice(R, size=8, replace=False)
    keep = np.setdiff1d(np.arange(R + K), lost)
    plan_d = fountain.peel_decode_plan(dcode, keep)
    if plan_d is None:
        raise RuntimeError("decoder code does not peel the chosen loss set")
    src = jax.random.normal(jax.random.PRNGKey(SEED + 1), (R * dbm, cols))
    coded = lt_encode_code(src, dcode, bm=dbm, use_pallas=True,
                           interpret=False)
    crx = coded.reshape(R + K, dbm, cols)[keep].reshape(-1, cols)
    dec = jax.block_until_ready(
        lt_decode(crx, plan_d, bm=dbm, use_pallas=True, interpret=False))
    ref = lt_decode_ref(crx, plan_d, bm=dbm)
    ph.rec.update(lt_R=R, lt_K=K, lt_cols=cols, lt_bm=dbm, lt_lost=len(lost),
                  lt_rounds=len(fountain.plan_rounds(plan_d)))
    # Both decode the same f32 payload by the same subtractions in a
    # different order: f32 rounding, well under 1e-4 of the scale.
    e = _rel_err(dec, ref)
    ph.check("lt_decode_vs_ref_rel_err", e, 1e-4, e <= 1e-4)
    e = _rel_err(dec, src)
    ph.check("lt_decode_vs_source_rel_err", e, 1e-4, e <= 1e-4)
    return ph.done()


# ---------------------------------------------------------------------------
# Phase 3: phi4-mini-3.8b serving at full width
# ---------------------------------------------------------------------------

def phase_serve(arch="phi4-mini-3.8b", batches=4, B=4, prompt=128, n_new=16,
                max_len=512):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.serve_loop import ServeEngine, init_params

    ph = Phase("serve")
    cfg = get_config(arch, param_dtype="bfloat16", compute_dtype="bfloat16")
    model = build_model(cfg)
    params = jax.block_until_ready(init_params(model, SEED))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    eng = ServeEngine(model, params, max_len=max_len)
    rng = np.random.default_rng(SEED)
    reqs = [rng.integers(0, cfg.vocab, size=(B, prompt)).astype(np.int32)
            for _ in range(batches)]
    outs = [eng.generate(r, n_new=n_new) for r in reqs]
    again, logits = eng.generate(reqs[0], n_new=n_new, return_logits=True)
    ph.rec.update(arch=arch, layers=cfg.n_layers, d_model=cfg.d_model,
                  vocab=cfg.vocab, params=int(n_params), dtype="bfloat16",
                  batches=batches, B=B, prompt=prompt, new_tokens=n_new,
                  max_len=max_len)
    finite = bool(np.isfinite(np.asarray(logits)).all())
    ph.check("logits_finite", finite, True, finite)
    same = bool(np.array_equal(outs[0], again))
    ph.check("greedy_deterministic", same, True, same)
    ok_range = all(o.shape == (B, n_new) and o.min() >= 0
                   and o.max() < cfg.vocab for o in outs)
    ph.check("tokens_in_vocab", ok_range, True, ok_range)

    # Teacher-forced: the uncached forward over prompt + generated tokens
    # sees the same inputs at every position the cached decode saw.  Both
    # run bf16 activations through 32 layers with different reduction
    # shapes (one query against a 512-slot cache vs all 144 at once), so
    # they differ by bf16 rounding carried through the depth: allowed
    # 5% of the logit scale (u = 2^-8 per rounding, a dozen roundings per
    # layer's residual path), and greedy picks may differ only at near-ties.
    full = jnp.concatenate([jnp.asarray(reqs[0]), jnp.asarray(again)], 1)
    ref = jax.jit(model.forward)(params, full)[:, prompt - 1:prompt - 1 + n_new]
    e = _rel_err(logits, ref)
    ph.check("cached_vs_uncached_logits_rel_err", e, 0.05, e <= 0.05)
    ph.rec["argmax_agreement"] = float(
        (jnp.argmax(ref, -1) == jnp.asarray(again)).mean())
    return ph.done()


# ---------------------------------------------------------------------------
# --chips 4: the coded matmul over a model mesh, and the sharded engine
# ---------------------------------------------------------------------------

def _device_use(arrays):
    """Devices holding a shard of any of ``arrays``, by id."""
    return sorted({s.device.id for arr in arrays
                   for s in arr.addressable_shards})


def phase_coded_matmul_mesh(n_chips, rows=8192, k=4096, n=4096, bm=256):
    import jax
    import jax.numpy as jnp

    from repro.core import coded_matmul
    from repro.launch.mesh import make_host_mesh

    ph = Phase("coded_matmul_mesh")
    mesh = make_host_mesh(data=1, model=n_chips)
    # One shard per chip: 48 coded blocks, 12 per chip, any chip may go.
    plan = coded_matmul.plan_coded_matmul(rows=rows, n_shards=n_chips,
                                          overhead=0.5, bm=bm)
    ph.rec.update(rows=rows, k=k, n=n, bm=bm, R=plan.code.R, K=plan.code.K,
                  chips=n_chips)
    ka, kx = jax.random.split(jax.random.PRNGKey(SEED))
    for dt in (jnp.float32, jnp.bfloat16):
        a = jax.random.normal(ka, (rows, k), jnp.float32).astype(dt)
        x = jax.random.normal(kx, (k, n), jnp.float32).astype(dt)
        out = _coded_matmul_case(ph, jnp.dtype(dt).name, plan, a, x,
                                 (n_chips - 1,), mesh=mesh)
        tag = jnp.dtype(dt).name
        ph.rec[f"{tag}_out_sharding"] = str(out.sharding.spec)
        used = _device_use([out])
        ph.check(f"{tag}_devices_with_shard", used, n_chips,
                 len(used) == n_chips)
    ph.rec["peak_bytes_per_device"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()]
    return ph.done()


def phase_engine_sharded(n_chips, reps=1024, R=8000):
    import jax
    import numpy as np

    from repro.configs.ccp_paper import FIG3
    from repro.core import engine, policies, simulator

    ph = Phase("engine_sharded")
    cfg = FIG3[1]
    keys = simulator.batch_keys(reps, SEED)
    devs = jax.devices()[:n_chips]
    sharded = engine.Engine(shard=True, devices=devs).run(cfg, "ccp", keys, R)
    with jax.default_device(devs[0]):
        single = engine.Engine().run(cfg, "ccp", keys, R, M_override=sharded.M)
    ph.rec.update(N=cfg.N, R=R, reps=reps, M=sharded.M, chips=n_chips)
    # Reps never communicate, so the split changes nothing: bitwise equal.
    for f in ("T", "r_n", "efficiency", "valid"):
        a, b = getattr(sharded, f), getattr(single, f)
        same = bool(np.array_equal(a, b))
        ph.rec[f"{f}_max_abs_diff"] = float(np.nanmax(np.abs(
            a.astype(np.float64) - b.astype(np.float64))))
        ph.check(f"{f}_identical", same, True, same)
    # Where the sharded batch lives: one shard of the reps on every chip.
    out = engine._sim_batch_sharded(keys, cfg, R, sharded.M,
                                    policies.get("ccp"), devs)
    used = _device_use([out["T"]])
    ph.rec["T_sharding"] = str(out["T"].sharding.spec)
    ph.check("devices_with_reps", used, n_chips, len(used) == n_chips)
    return ph.done()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the paths that span four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no src/repro package beside {__file__}; "
                 "run it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The simulator phase re-runs a few keys on the host CPU; keep that
    # backend where the platforms are pinned (the TPU still comes first).
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    import jax.monitoring

    from repro.utils import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("chip_smoke: no TPU found; JAX sees only "
                 f"{devices[0].platform} devices ({len(devices)})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, JAX sees {len(devices)}")
    jax.monitoring.register_event_duration_secs_listener(CLOCK)

    if args.chips == 1:
        phases = (phase_simulator, phase_coded_matmul, phase_serve)
    else:
        phases = (functools.partial(phase_engine_sharded, args.chips),
                  functools.partial(phase_coded_matmul_mesh, args.chips))
    failed = [f for phase in phases for f in phase()]
    if failed:
        sys.exit(f"chip_smoke: failed checks: {failed}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
