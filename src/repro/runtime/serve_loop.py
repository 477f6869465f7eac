"""Serving runtime: batched prefill/decode with CCP request dispatch.

One ``ServeEngine`` wraps a model + params and exposes generate() over
batched requests.  ``CCPDispatcher`` spreads request batches over multiple
(possibly heterogeneous) engine replicas using the paper's estimator: each
replica is a "helper", a batch is a "packet", and dispatch rates follow
E[beta] estimates with timeout backoff — the serving-side realization of
Algorithm 1.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.scheduler import CCPScheduler
from ..models.model import Model


def init_params(model: Model, seed: int = 0):
    """Random params from ``seed``, drawn inside one jitted program.

    ``ParamBuilder`` draws in f32 and casts; run eagerly, the layer
    params are alive twice at the peak (the per-layer list and its stack,
    2 x 6.4 GB at phi4-mini width in bf16, on a 16 GB chip).  Compiled for
    a TPU v5e, the jitted init writes its 7.7 GB of params with 36 MB of
    temporaries."""
    return jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(seed))


@dataclasses.dataclass
class ServeEngine:
    model: Model
    params: object
    max_len: int = 512
    sample: str = "greedy"

    def __post_init__(self):
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))

    def generate(
        self,
        tokens: np.ndarray,           # (B, T) prompts (right-aligned, padded)
        n_new: int,
        embeds: Optional[np.ndarray] = None,
        return_logits: bool = False,
    ):
        """Greedy-decode ``n_new`` tokens after each prompt: (B, n_new).

        ``return_logits`` also returns the (B, n_new, vocab) logits each
        token was picked from, for checks against an uncached forward."""
        B, T = tokens.shape
        cache = self.model.init_cache(B, self.max_len)
        toks = jnp.asarray(tokens)
        if embeds is not None:
            logits, cache = self._prefill(self.params, toks[:, :-1], cache,
                                          jnp.asarray(embeds))
        else:
            logits, cache = self._prefill(self.params, toks[:, :-1], cache)
        out, step_logits = [], []
        cur = toks[:, -1:]
        for _ in range(n_new):
            logits, cache = self._decode(self.params, cur, cache)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out.append(np.asarray(cur))
            if return_logits:
                step_logits.append(logits)
        out = np.concatenate(out, axis=1)
        if return_logits:
            return out, jnp.stack(step_logits, axis=1)
        return out


class CCPDispatcher:
    """Dispatch request batches over replicas with eq. (23) allocation."""

    def __init__(self, replicas: Sequence[Callable[[np.ndarray], np.ndarray]]):
        self.replicas = list(replicas)
        self.sched = CCPScheduler(n_workers=len(self.replicas))

    def run(self, batches: List[np.ndarray], rounds: Optional[int] = None):
        """Process batches round-by-round; per round, allocation follows the
        current E[beta] estimates. Returns (results, per_round_alloc)."""
        results = [None] * len(batches)
        allocs = []
        i = 0
        while i < len(batches):
            n_left = len(batches) - i
            alloc = self.sched.allocation(min(n_left, len(self.replicas) * 4))
            allocs.append(alloc.copy())
            durations = np.zeros(len(self.replicas))
            for w, n_w in enumerate(alloc):
                t0 = time.perf_counter()
                for _ in range(int(n_w)):
                    if i >= len(batches):
                        break
                    results[i] = self.replicas[w](batches[i])
                    i += 1
                durations[w] = time.perf_counter() - t0
            per_unit = np.where(alloc > 0, durations, np.nan)
            # feed only workers that actually ran something this round
            obs = np.where(alloc > 0, durations / np.maximum(alloc, 1), np.nan)
            obs = np.where(np.isnan(obs), np.nanmean(obs), obs)
            self.sched._work = np.maximum(alloc, 1)
            self.sched.observe_step(obs * np.maximum(alloc, 1))
        return results, allocs
