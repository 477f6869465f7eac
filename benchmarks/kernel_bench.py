"""Kernel-level roofline accounting for the Pallas hot spots (beyond-paper).

CPU wall-times of interpret-mode kernels are meaningless for TPU, so this
benchmark reports the *structural* roofline terms: FLOPs, HBM bytes moved
(fused vs. unfused), and arithmetic intensity — the quantities the §Perf
iterations act on — plus correctness spot-checks: the kernels against
their oracles, the vmapped Monte-Carlo engine against the sequential
per-rep loop, and the device-sharded batch against the single-device one.
Speeds are measured on the chip, by ``chipbench/``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, fountain, simulator
from repro.kernels.coded_matmul import coded_matmul, coded_matmul_ref
from repro.kernels.coded_matmul.ops import flops as cm_flops
from repro.kernels.flash_attention.ops import attention_flops

from .common import emit

HBM_BW = 819e9
PEAK = 197e12


def run() -> dict:
    rows = []
    # --- coded matmul: production-ish shapes ------------------------------
    for (R, K, bm, kdim, ndim) in ((32, 8, 256, 4096, 4096),
                                   (64, 16, 128, 8192, 1024)):
        code = fountain.make_lt_code(R, K, seed=0)
        d_mean = float(code.degrees().mean())
        f = cm_flops(R, K, bm, kdim, ndim, d_mean)
        ai_fused = f["matmul_flops"] / f["hbm_bytes_fused"]
        ai_unfused = f["matmul_flops"] / f["hbm_bytes_unfused"]
        rows.append({
            "kernel": "coded_matmul", "R": R, "K": K, "bm": bm,
            "k": kdim, "n": ndim,
            "matmul_flops": f["matmul_flops"],
            "encode_flops": f["encode_flops"],
            "bytes_fused": f["hbm_bytes_fused"],
            "bytes_unfused": f["hbm_bytes_unfused"],
            "fusion_byte_saving": 1 - f["hbm_bytes_fused"] / f["hbm_bytes_unfused"],
            "arith_intensity_fused": ai_fused,
            "arith_intensity_unfused": ai_unfused,
            "compute_bound_fused": ai_fused > PEAK / HBM_BW,
        })
    # correctness spot check (small, interpret mode)
    code = fountain.make_lt_code(8, 4, seed=1)
    a = jax.random.normal(jax.random.PRNGKey(0), (8 * 16, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    out = coded_matmul(a, x, jnp.asarray(code.idx), jnp.asarray(code.mask),
                       bm=16, use_pallas=True, interpret=True)
    ref = coded_matmul_ref(a, x, jnp.asarray(code.idx), jnp.asarray(code.mask), 16)
    max_err = float(jnp.abs(out - ref).max())

    # --- lt_decode: round-levelized peeling payload decode -----------------
    # Structural accounting per plan: the kernel executes one pallas_call
    # per dependency level (fountain.plan_rounds), so the device-side
    # critical path is the level count, not the O(R) sequential step count
    # of the peeling plan.  Pure VPU + DMA — memory bound by design.
    from repro.core import decode as decode_mod
    from repro.kernels.lt_decode import lt_decode

    for (R, K, bm, cols, n_lost) in ((64, 64, 256, 4096, 8),
                                     (256, 256, 64, 8192, 32)):
        dcode = decode_mod.make_decoder_code(R, K)
        rng = np.random.default_rng(R)
        lost = rng.choice(R, size=n_lost, replace=False)
        keep = np.setdiff1d(np.arange(R + K), lost)
        plan = fountain.peel_decode_plan(dcode, keep)
        if plan is None:
            # Peeling stall on this sampled loss pattern: record it instead
            # of structural numbers (the decode would take the dense path).
            rows.append({"kernel": "lt_decode", "R": R, "K": K, "bm": bm,
                         "cols": cols, "lost": n_lost, "peel_stalled": True})
            continue
        rounds = fountain.plan_rounds(plan)
        d_mean = float(np.mean([
            (rnd.nbr_coef != 0).sum(axis=1).mean() for rnd in rounds
        ])) if rounds else 0.0
        n_peel = sum(rnd.size for rnd in rounds)
        # per recovered source: read 1 coded + d_mean src tiles, write 1
        bytes_moved = 4.0 * bm * cols * (n_peel * (2.0 + d_mean)
                                         + 2.0 * plan.direct_src.size)
        flops = 2.0 * bm * cols * n_peel * (d_mean + 1.0)
        rows.append({
            "kernel": "lt_decode", "R": R, "K": K, "bm": bm, "cols": cols,
            "lost": n_lost, "plan_steps": plan.n_peeled,
            "rounds": len(rounds), "peel_d_mean": d_mean,
            "hbm_bytes": bytes_moved, "flops": flops,
            "arith_intensity": flops / bytes_moved,
            "seq_step_saving": 1.0 - len(rounds) / max(plan.n_peeled, 1),
        })
    # correctness spot check vs the jnp reference (small, interpret mode)
    dcode = decode_mod.make_decoder_code(12, 12, d_max=8)
    keep = np.setdiff1d(np.arange(24), [2, 7, 11])
    plan = fountain.peel_decode_plan(dcode, keep)
    blocks = jax.random.normal(jax.random.PRNGKey(2), (12 * 8, 32))
    from repro.kernels.lt_encode import lt_encode_code
    coded = lt_encode_code(blocks, dcode, bm=8)
    crx = coded.reshape(24, 8, 32)[keep].reshape(-1, 32)
    dec_ref = lt_decode(crx, plan, bm=8)
    dec_ker = lt_decode(crx, plan, bm=8, use_pallas=True, interpret=True,
                        bc=32)
    lt_decode_max_err = float(jnp.abs(dec_ker - dec_ref).max())
    lt_decode_recon_err = float(jnp.abs(dec_ref - blocks).max())

    # --- flash attention: assigned-shape accounting ------------------------
    for (tag, B, Hq, Tq, Tk, D, window) in (
        ("gemma2 train local", 32, 32, 4096, 4096, 128, 4096),
        ("gemma2 prefill32k global", 32, 32, 32768, 32768, 128, None),
        ("nemo decode32k", 128, 32, 1, 32768, 128, None),
    ):
        f = attention_flops(B, Hq, Tq, Tk, D, causal=True, window=window)
        io = 2.0 * B * (Hq * Tq * D * 2 + 2 * (Hq * Tk * D * 2) // max(Hq // 8, 1))
        naive_bytes = io + 4.0 * B * Hq * Tq * Tk  # materialized scores fp32
        rows.append({
            "kernel": "flash_attention", "case": tag,
            "flops": f, "bytes_flash": io, "bytes_naive": naive_bytes,
            "hbm_saving": 1 - io / naive_bytes,
        })
    # --- batched vs sequential Monte-Carlo (engine.Engine) -----------------
    # The vmapped batch and the sequential per-rep loop must agree on the
    # mean completion time in two regimes: fig5-style (N=10, per-rep
    # horizons vary with the mu draw) and fig3-style (N=100).
    eng = engine.Engine()
    for tag, cfg, R in (
        ("fig5", simulator.ScenarioConfig(N=10, scenario=2,
                                          rate_lo=0.1e6, rate_hi=0.2e6), 400),
        ("fig3", simulator.ScenarioConfig(N=100, scenario=1), 2000),
    ):
        reps = 40
        keys = simulator.batch_keys(reps)
        batched = eng.run(cfg, "ccp", keys, R)
        seq_t = [eng.run_one(keys[r], cfg, "ccp", R)["T"]
                 for r in range(reps)]
        rows.append({
            "kernel": "mc_batch", "case": tag, "reps": reps, "R": R,
            "N": cfg.N, "M": batched["M"],
            "mc_mean_abs_gap": abs(float(np.mean(batched["T"]))
                                   - float(np.mean(seq_t))),
        })

    # --- device-sharded vs single-device batched MC ------------------------
    # Per-rep lanes are independent, so the sharded batch must be bitwise
    # the single-device one.
    cfg, R, reps = simulator.ScenarioConfig(N=100, scenario=1), 2000, 40
    keys = simulator.batch_keys(reps)
    un = eng.run(cfg, "ccp", keys, R)
    sh = eng.run(cfg, "ccp", keys, R, shard=True)
    shard_eq = bool(np.array_equal(un["T"], sh["T"]))
    rows.append({
        "kernel": "mc_batch_shard", "devices": jax.local_device_count(),
        "reps": reps, "R": R, "bitwise_equal": shard_eq,
    })

    emit("kernel_bench", rows,
         derived=f"coded_matmul_max_err={max_err:.2e};"
                 f"lt_decode_max_err={lt_decode_max_err:.2e};"
                 f"lt_decode_recon_err={lt_decode_recon_err:.2e};"
                 f"mc_shard_bitwise_equal={shard_eq}")
    return {"rows": rows, "max_err": max_err,
            "lt_decode_max_err": lt_decode_max_err,
            "mc_shard_equal": shard_eq}


if __name__ == "__main__":
    out = run()
    for r in out["rows"]:
        print(" ", {k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in list(r.items())[:6]})
