"""Distributed coded matmul over the 'model' mesh axis (shard_map).

The paper's end-to-end object: y = A x computed by N workers holding
fountain-coded row-blocks, tolerant to any K worker losses.  Each device
holds a contiguous slice of the coded block space (systematic blocks +
parities interleaved round-robin so losing a device loses a *spread* of
blocks, not a contiguous run); compute is the fused Pallas kernel (or jnp
fallback); a lost device is modeled by a survivor mask and the collector
recovers y by peeling/dense decode.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.coded_matmul import coded_matmul as coded_matmul_op
from . import fountain

__all__ = ["CodedMatmulPlan", "plan_coded_matmul", "device_blocks", "run", "recover"]


@dataclasses.dataclass(frozen=True)
class CodedMatmulPlan:
    """Static plan: code + device->coded-block placement for n_shards."""

    code: fountain.LTCode
    n_shards: int
    placement: np.ndarray      # (n_shards, blocks_per_shard) coded ids
    bm: int                    # rows per block

    @property
    def blocks_per_shard(self) -> int:
        return self.placement.shape[1]


def plan_coded_matmul(
    rows: int, n_shards: int, overhead: float = 0.25, bm: int = 128,
    seed: int = 0, validate_losses: int = 1, max_tries: int = 50,
) -> CodedMatmulPlan:
    """Split an (rows x k) matrix into bm-row blocks, build a systematic LT
    code with ~``overhead`` parities rounded so every shard holds the same
    block count, and place blocks round-robin across shards.

    Placement-aware validation: on a mesh the unit of failure is a *shard*
    (a whole device's blocks at once), so the plan is rank-checked against
    every loss pattern of up to ``validate_losses`` shards and re-seeded
    until all decode — turning the fountain code's probabilistic contract
    into a deterministic per-plan guarantee (cf. Raptor pre-validation)."""
    if rows % bm:
        raise ValueError(f"rows={rows} not divisible by bm={bm}")
    R = rows // bm
    K = int(np.ceil(R * overhead))
    total = R + K
    if total % n_shards:  # pad K so shards are uniform
        K += n_shards - total % n_shards
    ids = np.arange(R + K)
    placement = np.stack([ids[s::n_shards] for s in range(n_shards)])

    import itertools

    last_err = None
    for t in range(max_tries):
        # dense ±1 parities: encode adds are VPU-cheap next to the fused
        # MXU matmul, and small-block shard-loss patterns become
        # generically full-rank (see fountain.make_lt_code docstring)
        code = fountain.make_lt_code(
            R, K, seed=seed + 7919 * t, parity_degree=max(R // 2, 4)
        )
        if validate_losses <= 0:
            return CodedMatmulPlan(code, n_shards, placement, bm)
        G = code.dense_generator()
        ok = True
        for r in range(1, validate_losses + 1):
            for lost in itertools.combinations(range(n_shards), r):
                keep = np.setdiff1d(np.arange(n_shards), lost)
                rx = placement[keep].reshape(-1)
                if np.linalg.matrix_rank(G[rx]) < R:
                    ok = False
                    break
            if not ok:
                break
        if ok:
            return CodedMatmulPlan(code, n_shards, placement, bm)
        last_err = f"seed {seed + 7919 * t} fails a {r}-shard loss pattern"
    raise ValueError(
        f"no code tolerating {validate_losses}-shard losses found in "
        f"{max_tries} tries (R={R}, K={K}, shards={n_shards}); raise the "
        f"overhead. Last: {last_err}"
    )


def device_blocks(plan: CodedMatmulPlan, a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gather per-device (idx, weights) tables in placement order:
    returns (idx (S*Bp, d_max), weights (S*Bp, d_max)) where row s*Bp+i is
    the i-th coded block on shard s (weights = mask * Rademacher coef)."""
    flat = plan.placement.reshape(-1)
    return (
        jnp.asarray(plan.code.idx[flat]),
        jnp.asarray(plan.code.weights[flat]),
    )


def run(
    plan: CodedMatmulPlan,
    a: jnp.ndarray,             # (rows, k_dim) source matrix
    x: jnp.ndarray,             # (k_dim, n_dim)
    mesh: Optional[Mesh] = None,
    axis: str = "model",
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Compute all coded block products, laid out shard-major:
    out[s*Bp+i] = (G A)[placement[s, i]] @ x, shape (S*Bp*bm, n_dim).

    With a mesh, the coded-row dim is sharded over ``axis`` via shard_map —
    each device encodes+computes only its own blocks (the paper's helpers).
    """
    idx, mask = device_blocks(plan, a)

    def local(a_full, x_full, idx_s, mask_s):
        return coded_matmul_op(
            a_full, x_full, idx_s, mask_s, bm=plan.bm,
            use_pallas=use_pallas, interpret=interpret,
        )

    if mesh is None:
        return local(a, x, idx, mask)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(a, x, idx, mask)


def recover(
    plan: CodedMatmulPlan,
    out: jnp.ndarray,           # (S*Bp*bm, n_dim) coded results
    survivors: np.ndarray,      # shard ids that returned
) -> jnp.ndarray:
    """Collector-side recovery of y = A x from surviving shards only."""
    Bp, bm = plan.blocks_per_shard, plan.bm
    rows = []
    ids = []
    for s in survivors:
        sl = out[s * Bp * bm : (s + 1) * Bp * bm]
        rows.append(sl.reshape(Bp, bm, -1))
        ids.extend(plan.placement[s].tolist())
    coded_rx = jnp.concatenate(rows, axis=0)  # (n_rx, bm, n_dim)
    dec, _ = fountain.decode(coded_rx, plan.code, np.asarray(ids))
    return dec.reshape(plan.code.R * bm, -1)
