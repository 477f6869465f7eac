"""Training step factories: microbatched/remat GSPMD step + coded-DP step.

``make_train_step`` builds the production step: gradient accumulation over a
``lax.scan`` of microbatches (fp32 accumulator), remat per layer group,
AdamW update — this is what the multi-pod dry-run lowers.

``make_coded_train_step`` is the paper's contribution wired into DP: an
explicit ``shard_map`` over the 'data' axis where every worker computes its
own microbatch gradient plus (round-robin) one parity gradient — the
gradient of a sparse sum of neighbour microbatches — and aggregation is a
*weighted* psum whose weights (a tiny input) realize the R-of-(R+K) decode
for the current survivor set.  Straggler/failure tolerance without
recompilation; the no-straggler weight pattern makes the parity term a
no-op add.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core import gradient_coding
from ..models.model import Model
from ..optim import adamw

PyTree = Any


def _reshape_micro(batch: Dict[str, jnp.ndarray], n_micro: int):
    def r(x):
        b = x.shape[0]
        assert b % n_micro == 0, f"batch {b} % n_micro {n_micro}"
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])

    return {k: r(v) for k, v in batch.items() if v is not None}


def make_train_step(
    model: Model,
    opt_cfg: adamw.AdamWConfig,
    n_microbatches: int = 1,
    pre_shaped: bool = False,
    unroll: bool = False,
) -> Callable:
    """(params, opt_state, batch) -> (params', opt_state', metrics).

    ``pre_shaped``: batch arrays already carry the leading (n_micro, mb, ...)
    layout (the data pipeline / dry-run produce this so no cross-shard
    reshape of the batch dim is compiled in).
    ``unroll``: unroll the microbatch scan (dry-run cost-analysis fidelity).
    """

    def train_step(params, opt_state, batch):
        mb = batch if pre_shaped else _reshape_micro(batch, n_microbatches)

        def micro(carry, b):
            gsum, lsum = carry
            loss, grads = jax.value_and_grad(model.loss_fn)(params, b)
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads
            )
            return (gsum, lsum + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = jax.lax.scan(
            micro, (zeros, jnp.zeros(())), mb, unroll=unroll
        )
        grads = jax.tree.map(lambda g: g / n_microbatches, gsum)
        params, opt_state, metrics = adamw.apply(opt_cfg, params, grads, opt_state)
        metrics["loss"] = lsum / n_microbatches
        return params, opt_state, metrics

    return train_step


def make_eval_step(model: Model) -> Callable:
    def eval_step(params, batch):
        return model.loss_fn(params, batch)

    return eval_step


# ---------------------------------------------------------------------------
# Coded data parallelism (the paper's technique in the training loop)
# ---------------------------------------------------------------------------

def make_coded_train_step(
    model: Model,
    opt_cfg: adamw.AdamWConfig,
    mesh: Mesh,
    n_parity: Optional[int] = None,
    axis: str = "data",
    seed: int = 0,
):
    """Coded-DP training step over ``axis`` (R workers = axis size).

    Returns (train_step, code, weight_table) where
      train_step(params, opt_state, batch, weights) and
      batch["tokens"]: (R * mb, T) sharded over ``axis``,
      weights: (R+K',) decode weights — K' = parities *padded to R* so every
      worker runs exactly one parity pass (zero-degree pads contribute
      nothing; uniform compute keeps the step shape static).
    """
    R = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    K = n_parity if n_parity is not None else max(1, R // 4)
    code = gradient_coding.make_gradient_code(R, K, seed=seed)
    assigns = gradient_coding.parity_assignments(code)
    # worker w's parity: the k with k % R == w (or empty)
    per_worker = [[] for _ in range(R)]
    for k, nbrs in enumerate(assigns):
        per_worker[k % R].append((k, nbrs))
    d_max = max((len(n) for _, ns in enumerate(assigns) for n in [ns]), default=1)
    # parity neighbour table per worker: (R, d_max) source ids + coefficients
    nbr = np.zeros((R, d_max), np.int32)
    nmask = np.zeros((R, d_max), np.float32)
    pid = np.full((R,), -1, np.int32)  # which coded row this worker's parity is
    for w in range(R):
        if per_worker[w]:
            k, nbrs = per_worker[w][0]  # one parity per worker max (K <= R)
            row = code.R + k
            pid[w] = row
            nbr[w, : len(nbrs)] = nbrs
            # coefficient of each neighbour in this parity row
            cmap = {int(s): float(c) for s, c in
                    zip(code.idx[row][code.mask[row]],
                        code.coef[row][code.mask[row]])}
            nmask[w, : len(nbrs)] = [cmap[int(s)] for s in nbrs]
    nbr_j = jnp.asarray(nbr)
    nmask_j = jnp.asarray(nmask)
    pid_j = jnp.asarray(pid)

    def local_grads(params, batch_all, weights):
        """Runs per-device under shard_map: batch_all (R, mb, T) replicated
        (each worker reads its own + neighbour microbatches)."""
        w_idx = jax.lax.axis_index(axis)
        own = jax.tree.map(lambda x: x[w_idx], batch_all)
        _, g_own = jax.value_and_grad(model.loss_fn)(params, own)

        def parity_loss(p):
            mbs = jax.tree.map(lambda x: x[nbr_j[w_idx]], batch_all)  # (d_max, mb, T)
            losses = jax.vmap(lambda b: model.loss_fn(p, b))(
                jax.tree.map(lambda x: x, mbs)
            )
            return (losses * nmask_j[w_idx]).sum()

        g_par = jax.grad(parity_loss)(params)
        w_own = weights[w_idx]
        w_par = jnp.where(pid_j[w_idx] >= 0,
                          weights[jnp.maximum(pid_j[w_idx], 0)], 0.0)
        combined = jax.tree.map(
            lambda a, b: (w_own * a.astype(jnp.float32)
                          + w_par * b.astype(jnp.float32)),
            g_own, g_par,
        )
        summed = jax.tree.map(
            lambda g: jax.lax.psum(g, axis), combined
        )
        loss = jax.lax.psum(model.loss_fn(params, own) * w_own, axis)
        return summed, loss

    sharded = jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    def train_step(params, opt_state, batch_all, weights):
        grads, loss = sharded(params, batch_all, weights)
        grads = jax.tree.map(lambda g: g / R, grads)
        params, opt_state, metrics = adamw.apply(opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss / R
        return params, opt_state, metrics

    pats, ws = gradient_coding.weight_table(code, max_stragglers=max(1, K // 2), seed=seed)
    return train_step, code, (pats, ws)
