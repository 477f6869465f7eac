"""Generator of Monte-Carlo sweep traffic for the simulator (``Engine.run``).

A closed loop: batches of ``reps_per_batch`` reps go through ``Engine.run``
back to back, each batch's keys drawn from (seed, batch index).  A rep that
``Engine.run`` does not certify is a failed operation: it is counted, never
averaged.  After the window a sample of reps drawn from the seed, with the
rep of the longest completion time in it, is re-run by the plain reference
(``references/<config reference>.py``) and compared.

Traffic parameters (``traffic/<mix>.json``): ``policy`` (registry name),
``churn`` (null or ``ChurnConfig`` fields), ``reps_per_batch``,
``check_reps`` (sample size).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from .common import Checks, numpy_seed, root_key

WARM_BATCH = 0xFFFFFFFF   # batch index of the warm-up batch's keys


def scenario(config: dict, traffic: dict):
    """The program's ``ScenarioConfig`` for this deployment and mix."""
    from repro.core.simulator import ChurnConfig, ScenarioConfig

    churn = traffic.get("churn")
    return ScenarioConfig(
        N=int(config["N"]), scenario=int(config["scenario"]),
        mu_choices=tuple(float(m) for m in config["mu_choices"]),
        a_mode="const", a_const=float(config["a_const"]),
        rate_lo=float(config["rate_lo_bps"]),
        rate_hi=float(config["rate_hi_bps"]),
        overhead=float(config["overhead"]), alpha=float(config["alpha"]),
        churn=None if churn is None else ChurnConfig(**churn))


class Workload:
    def __init__(self, config, traffic, limits, seed, chips, spans):
        import jax

        from repro.core import engine, policies

        if chips != 1:
            raise ValueError("simulator cells run on one chip")
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.spans = seed, spans
        self.cfg = scenario(config, traffic)
        self.R = int(config["R"])
        self.B = int(traffic["reps_per_batch"])
        self.policy = policies.get(traffic["policy"])
        self.engine = engine.Engine()
        self.root = root_key(seed)
        B = self.B
        self._keys = jax.jit(lambda root, b: jax.vmap(
            lambda r: jax.random.fold_in(jax.random.fold_in(root, b), r))(
                jax.numpy.arange(B, dtype=jax.numpy.uint32)))
        self.batches = []        # (b, M, T, valid, r_n, efficiency)
        self.devices = [jax.devices()[0]]

    def keys(self, b: int):
        return self._keys(self.root, np.uint32(b))

    def warm(self):
        """Compile the key maker and the batch program at the horizon that
        certifies, by one batch of keys the window never uses."""
        res = self.engine.run(self.cfg, self.policy, self.keys(WARM_BATCH),
                              self.R)
        self.warm_M = res.M

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        b = 0
        while True:
            with self.spans.span("batch"):
                res = self.engine.run(self.cfg, self.policy, self.keys(b),
                                      self.R)
            self.batches.append((b, res.M, res.T, res.valid, res.r_n,
                                 res.efficiency))
            b += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        attempted = b * self.B
        certified = int(sum(int(v.sum()) for _, _, _, v, _, _ in self.batches))
        return dict(seconds=elapsed, attempted=attempted,
                    failed=attempted - certified,
                    metrics={"reps_per_s": certified / elapsed},
                    work={"reps": certified, "batches": b,
                          "horizons": sorted({m for _, m, *_ in self.batches})})

    def release(self):
        """Drop the program's state; the window's results stay on the host."""
        self.engine = None
        self._keys = None

    def sample(self):
        """(batch, rep) pairs to check: ``check_reps`` drawn from the seed,
        and the rep whose completion time was longest."""
        n = int(self.traffic["check_reps"])
        total = len(self.batches) * self.B
        rng = numpy_seed(self.seed, 1)
        flat = rng.choice(total, size=min(n, total), replace=False)
        picks = {(int(i) // self.B, int(i) % self.B) for i in flat}
        T = np.concatenate([t for _, _, t, _, _, _ in self.batches])
        valid = np.concatenate([v for _, _, _, v, _, _ in self.batches])
        longest = int(np.argmax(np.where(valid, T, -np.inf)))
        picks.add((longest // self.B, longest % self.B))
        return sorted(picks)

    def program_answer(self, b, r):
        _, M, T, valid, r_n, eff = self.batches[b]
        return M, dict(T=float(T[r]), valid=bool(valid[r]),
                       r_n=np.asarray(r_n[r], np.int64),
                       efficiency=np.asarray(eff[r], np.float64))

    def reference(self, dtype=np.float64):
        """Reference answers for the sample, computed in ``dtype``."""
        import jax

        ref = importlib.import_module(
            f"chipbench.references.{self.config['reference']}")
        out = {}
        for b, r in self.sample():
            M, _ = self.program_answer(b, r)
            key = jax.random.fold_in(jax.random.fold_in(self.root, b), r)
            out[(b, r)] = ref.simulate(ref.draws(key, self.config, M),
                                       self.config, M, dtype)
        return out

    def compare(self, answers: dict, reference: dict) -> Checks:
        """Worst gaps over the sample between ``answers`` and the reference:
        completion time (relative), packets moved between helpers,
        efficiency (absolute)."""
        ck = Checks(self.limits)
        t_gap = moved = e_gap = 0.0
        for k, ref in reference.items():
            got = answers[k]
            t_gap = max(t_gap, abs(got["T"] - ref["T"]) / abs(ref["T"]))
            moved = max(moved, int(np.abs(got["r_n"] - ref["r_n"]).sum()))
            both_nan = np.isnan(got["efficiency"]) & np.isnan(ref["efficiency"])
            gap = np.where(both_nan, 0.0,
                           np.abs(got["efficiency"] - ref["efficiency"]))
            e_gap = max(e_gap, float(np.nan_to_num(gap, nan=np.inf).max()))
        ck.add("T_rel_gap", float(t_gap))
        ck.add("r_n_moved", int(moved))
        ck.add("efficiency_gap", float(e_gap))
        return ck

    def check(self) -> Checks:
        reference = self.reference(np.float64)
        answers = {k: self.program_answer(*k)[1] for k in reference}
        return self.compare(answers, reference)

    def control(self) -> Checks:
        """The reference in bfloat16 in the program's place."""
        import ml_dtypes

        reference = self.reference(np.float64)
        low = self.reference(ml_dtypes.bfloat16)
        return self.compare(low, reference)
