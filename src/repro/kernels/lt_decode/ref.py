"""Pure-jnp reference for the round-based LT payload decode.

The offline :func:`repro.core.fountain.apply_decoder` applies the
peeling schedule as one (R, n_rx) matrix formed on the host.  The kernel
path instead executes the :func:`repro.core.fountain.plan_rounds`
levelization on the device:
every source of a round is recovered by one batched masked gather +
subtract, so the device-side critical path is the dependency depth
(typically O(log R)) rather than T.  This module is the jnp oracle the
Pallas kernel is pinned against — and the dispatch fallback off-TPU.
"""

from __future__ import annotations

import jax.numpy as jnp

from ...core import fountain


def peel_round_ref(src, coded, rnd, *, bm: int):
    """Apply one :class:`~repro.core.fountain.PlanRound` to the source
    buffer.

    src:   (R, bm, n_cols) partially recovered source blocks.
    coded: (n_rx, bm, n_cols) received coded blocks.
    Returns the (S, bm, n_cols) newly recovered blocks for ``rnd.src``.
    """
    gathered = src[jnp.asarray(rnd.nbr_idx)]          # (S, d_max, bm, cols)
    w = jnp.asarray(rnd.nbr_coef).astype(src.dtype)[:, :, None, None]
    piv = jnp.asarray(rnd.pivot).astype(src.dtype)[:, None, None]
    return (coded[jnp.asarray(rnd.coded)] - (gathered * w).sum(axis=1)) / piv


def lt_decode_ref(coded_rx: jnp.ndarray, plan: fountain.DecodePlan,
                  *, bm: int) -> jnp.ndarray:
    """Round-based peeling decode: (n_rx * bm, n_cols) -> (R * bm, n_cols).

    Bit-compatible with the Pallas kernel path (same round schedule, same
    accumulation order) and numerically equal to
    :func:`fountain.apply_decoder` up to fp addition order.
    """
    n_cols = coded_rx.shape[1]
    n_rx = coded_rx.shape[0] // bm
    coded = coded_rx.reshape(n_rx, bm, n_cols)
    src = jnp.zeros((plan.R, bm, n_cols), coded_rx.dtype)
    if plan.direct_src.size:
        dcoef = jnp.asarray(plan.direct_coef).astype(src.dtype)[:, None, None]
        src = src.at[jnp.asarray(plan.direct_src)].set(
            coded[jnp.asarray(plan.direct_coded)] / dcoef
        )
    for rnd in fountain.plan_rounds(plan):
        vals = peel_round_ref(src, coded, rnd, bm=bm)
        src = src.at[jnp.asarray(rnd.src)].set(vals)
    return src.reshape(plan.R * bm, n_cols)
