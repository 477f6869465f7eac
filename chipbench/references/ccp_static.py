"""Plain reference of one Monte-Carlo rep of CCP on a static helper pool.

Written from the paper (arXiv:1801.04357, §3-§6 and Algorithm 1), sequential
in the packet index and vectorised over helpers with numpy, in a dtype of
the caller's choosing: float64 is the reference, bfloat16 the control.  It
imports nothing of the program under test.  The random draws follow the
deployment's scenario model with ``jax.random`` from the rep's key:

  key -> (k_h, k_p);  k_h -> (k1, k2): mu ~ choice(mu_choices), rate ~ U(lo, hi)
  k_p -> (kb, ku, kd): beta = a + Exp/mu (Scenario 1, per packet),
                       up/down rates ~ max(Poisson(rate in Mbps), 0.5) Mbps
  d_up = Bx/up, d_ack = Back/down, d_down = Br/down
  (Bx = packet_bits_per_row * R, Br = result_bits, Back = ack_bits)

Per helper and packet i (eq. 2-8, causal form of eq. 8):

  arrive = tx + d_up;  start = max(arrive, done_prev);  done = start + beta
  tr = done + d_down;  idle = max(arrive - done_prev, 0)
  RTT^data EWMA (eq. 3-4), Tu (eq. 7), E[beta] = (Tc - Tu)/m (eq. 5-6)
  tx_next = min(tr, tx + E[beta]_sel), E[beta]_sel the estimate of the
  latest result (of the last 16 in flight) returned by tx; tr if none.

The task completes at the (R+K)-th result arrival T; it is certified when
no helper's M-th result arrives before T.  Per helper, r_n counts results
by T and efficiency is 1 - idle/(idle + busy) over them.
"""

from __future__ import annotations

import numpy as np

RING = 16


def draws(key, c: dict, M: int):
    """The rep's helper and packet draws, float32 numpy arrays."""
    import jax
    import jax.numpy as jnp

    N = int(c["N"])
    k_h, k_p = jax.random.split(key)
    k1, k2 = jax.random.split(k_h)
    mu = jax.random.choice(k1, jnp.asarray(c["mu_choices"], jnp.float32),
                           shape=(N,))
    rate = jax.random.uniform(k2, (N,), minval=float(c["rate_lo_bps"]),
                              maxval=float(c["rate_hi_bps"]))
    kb, ku, kd = jax.random.split(k_p, 3)
    exp = jax.random.exponential(kb, (N, M))
    lam = jnp.broadcast_to((rate / 1e6)[:, None], (N, M))
    up = jax.random.poisson(ku, lam, (N, M)).astype(jnp.float32)
    dn = jax.random.poisson(kd, lam, (N, M)).astype(jnp.float32)
    return {k: np.asarray(v) for k, v in
            dict(mu=mu, exp=exp, up=up, dn=dn).items()}


def simulate(d: dict, c: dict, M: int, dtype=np.float64) -> dict:
    """One rep from its draws ``d``; returns T, valid, r_n, efficiency."""
    dt = np.dtype(dtype)
    f = lambda v: np.asarray(v, dt)
    one, zero, inf = f(1.0), f(0.0), f(np.inf)
    R = int(c["R"])
    kk = R + int(np.ceil(float(c["overhead"]) * R))
    Bx = float(c["packet_bits_per_row"]) * R
    Br, Back = float(c["result_bits"]), float(c["ack_bits"])
    alpha = f(c["alpha"])
    data_scale = f((Bx + Br) / (Bx + Back))
    back_frac = f(Br / (Bx + Br))
    fwd_frac = f(Bx / (Bx + Back))
    a = f(c["a_const"])
    beta = a + f(d["exp"]) / f(d["mu"])[:, None]
    up = np.maximum(f(d["up"]), f(0.5)) * f(1e6)
    dn = np.maximum(f(d["dn"]), f(0.5)) * f(1e6)
    d_up, d_ack, d_down = f(Bx) / up, f(Back) / dn, f(Br) / dn

    N = beta.shape[0]
    tx = np.zeros(N, dt)
    done_prev = np.zeros(N, dt)
    tr_prev = np.zeros(N, dt)
    rtt_data = np.zeros(N, dt)
    Tu = np.zeros(N, dt)
    m = np.zeros(N, np.int64)
    ring_tr = np.full((N, RING), inf, dt)
    ring_eb = np.zeros((N, RING), dt)
    tr = np.empty((N, M), dt)
    idle = np.empty((N, M), dt)
    rows = np.arange(N)
    for i in range(M):
        arrive = tx + d_up[:, i]
        start = np.maximum(arrive, done_prev)
        done = start + beta[:, i]
        tr_i = done + d_down[:, i]
        idle[:, i] = np.maximum(arrive - done_prev, zero)
        tr[:, i] = tr_i
        # Algorithm 1, on a computed packet's return (eq. 3-7).
        first = m == 0
        sample = data_scale * (d_up[:, i] + d_ack[:, i])
        rtt_new = np.where(first, sample,
                           alpha * sample + (one - alpha) * rtt_data)
        Tu = np.where(first, fwd_frac * (d_up[:, i] + d_ack[:, i]),
                      Tu + np.maximum(zero, rtt_new - (tr_prev - tx)))
        m = m + 1
        e_beta = np.maximum((tr_i - back_frac * rtt_new - Tu) / f(m),
                            f(1e-9))
        rtt_data = rtt_new
        ring_tr[:, i % RING] = tr_i
        ring_eb[:, i % RING] = e_beta
        # eq. (8): the estimate in effect at tx is that of the latest
        # result returned by tx.
        seen = ring_tr <= tx[:, None]
        sel = np.argmax(np.where(seen, ring_tr, -inf), axis=1)
        eb_sel = ring_eb[rows, sel]
        tx = np.where(seen.any(axis=1), np.minimum(tr_i, tx + eb_sel), tr_i)
        done_prev = done
        tr_prev = tr_i
    T = np.sort(tr.reshape(-1))[kk - 1]
    within = tr <= T
    idle_sum = (idle * within).sum(axis=1, dtype=dt)
    busy_sum = (beta * within).sum(axis=1, dtype=dt)
    with np.errstate(invalid="ignore", divide="ignore"):
        eff = np.where(busy_sum > 0, one - idle_sum / (idle_sum + busy_sum),
                       f(np.nan))
    if tr.dtype != dt or eff.dtype != dt:
        raise TypeError(f"reference left {dt}: {tr.dtype}, {eff.dtype}")
    return dict(T=float(T), valid=bool(T <= tr[:, -1].min()),
                r_n=within.sum(axis=1).astype(np.int64),
                efficiency=eff.astype(np.float64))
