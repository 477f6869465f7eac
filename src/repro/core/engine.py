"""Policy-driven simulation engine: one entry point for every offloading
policy.

The engine owns the *scenario dynamics* — helper draws, per-packet
link/compute timing, and the churn loss processes (phase outages,
Gilbert–Elliott burst loss, correlated cell outages, slowdowns) — and
threads a :class:`~repro.core.policies.base.Policy` through the per-packet
``lax.scan``: the policy decides pacing (``next_load``), receipt handling
(``on_computed``), loss reaction (``on_timeout``) and the completion rule
(``finalize``).  Because the policy hooks are pure jnp functions, every
registered policy — including the block baselines and the adaptive
code-rate policy — runs jitted, vmapped over Monte-Carlo reps, and
device-sharded through the exact same code path.

Typical usage::

    from repro.core import engine, policies, simulator

    eng = engine.Engine()
    keys = simulator.batch_keys(reps=40)
    res = eng.run(cfg, "adaptive_rate", keys, R=2000)   # name or Policy
    res.T, res.efficiency, res.valid                    # RunResult pytree

The PR-2 string-dispatch surface (``simulator.run_batch(mode=...)``,
``run_ccp/best/naive/naive_oracle``, ``simulate_stream(mode=...)``) was
removed in PR 4; the golden tests in ``tests/test_policies.py`` still pin
``Engine.run`` bit-for-bit against its recorded outputs.

PR 7 factored the scan step into shared kernels (``_churn_step`` /
``_ge_step`` / ``_decode_step`` / ``_hook_step``) so the multi-tenant
event-clock scan of :mod:`repro.core.fleet` runs the exact same per-stream
ops with helper busy-time serialized across tenants;
``Engine.run_fleet(cfg, policy, keys, R, fleet=FleetConfig(...))`` is the
fleet entry point (see docs/fleet.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ccp as ccp_mod
from . import decode as decode_mod
from . import policies as policies_mod
from . import simulator as sim
from . import transport as transport_mod

__all__ = ["Engine", "RunResult", "FleetRunResult", "policy_stream"]


def _as_policy(policy) -> policies_mod.Policy:
    if isinstance(policy, str):
        return policies_mod.get(policy)  # unknown names raise with known list
    if not isinstance(policy, policies_mod.Policy):
        raise TypeError(
            "policy must be a registry name or a Policy instance, got "
            f"{type(policy).__name__}; known policies: "
            f"{list(policies_mod.names())}"
        )
    return policy


def _check_inputs(keys, R):
    """Actionable validation for the public runners: an empty key batch or
    a non-positive R otherwise surfaces as an opaque scan/shape error deep
    inside jit."""
    if isinstance(R, bool) or not isinstance(R, (int, np.integer)) or R <= 0:
        raise ValueError(
            f"R must be a positive int (source packets per task), got {R!r}"
        )
    keys = jnp.asarray(keys)
    if keys.ndim == 0 or keys.shape[0] == 0:
        raise ValueError(
            "keys must be a non-empty batch of PRNG keys — e.g. "
            f"simulator.batch_keys(reps) — got shape {tuple(keys.shape)}"
        )
    typed = jnp.issubdtype(keys.dtype, jax.dtypes.prng_key)
    if not (typed and keys.ndim == 1) and not (
            keys.ndim == 2 and keys.shape[-1] == 2):
        raise ValueError(
            "keys must be raw PRNG keys shaped (reps, 2) "
            "(simulator.batch_keys) or a 1-D typed key array; got shape "
            f"{tuple(keys.shape)} dtype {keys.dtype}"
        )
    return keys


# ---------------------------------------------------------------------------
# Shared step kernels
#
# The per-step physics — churn evaluation, the Gilbert–Elliott chain, the
# incremental decoder absorb, and the policy-hook round — are factored out
# of ``policy_stream``'s step so the multi-tenant event-clock scan
# (:mod:`repro.core.fleet.stream`) composes the *same traced ops* per
# (task, helper) stream.  That is what makes the 1-task dedicated-pool
# fleet bit-for-bit equal to the single-task path (tests/test_fleet.py).
# ---------------------------------------------------------------------------

def _parse_churn_static(churn_static):
    """Unpack ``ChurnConfig.static_key()`` — the current 6-tuple, the
    pre-transport 5-tuple, or the legacy 2-tuple (phase outages only)
    used by direct ``policy_stream`` callers."""
    ge_on = cell_on = False
    outage_dist = "phase"
    rtt_dist = "off"
    if len(churn_static) == 2:
        period, max_backoff = churn_static
    elif len(churn_static) == 5:
        period, max_backoff, outage_dist, ge_on, cell_on = churn_static
    else:
        (period, max_backoff, outage_dist, ge_on, cell_on,
         rtt_dist) = churn_static
    return period, max_backoff, outage_dist, ge_on, cell_on, rtt_dist


def _churn_step(dyn, a, beta_x, drop, t_arr, t_sta, sent, *, period, window,
                outage_dist, cell_on):
    """Outage / slowdown / iid-drop evaluation for one step's (N,) packets.

    Outage if the helper is down when the packet arrives or when it would
    start computing; degraded phases stretch the runtime (beta = a + eps/mu,
    so (beta - a)/speed rescales the random part).  ``t_arr``/``t_sta``
    must be pre-clamped for unsent slots so no inf reaches an index op.
    """
    if outage_dist == "phase":
        is_up = (sim._phase_lookup(dyn["up"], t_arr, period)
                 & sim._phase_lookup(dyn["up"], t_sta, period))
    else:
        is_up = ~(sim._interval_hit(dyn["out_start"], dyn["out_end"],
                                    t_arr, window)
                  | sim._interval_hit(dyn["out_start"], dyn["out_end"],
                                      t_sta, window)).any(axis=1)
    if cell_on:
        in_cell = dyn["cell_mask"] & (
            sim._interval_hit(dyn["cell_start"], dyn["cell_end"],
                              t_arr, window)
            | sim._interval_hit(dyn["cell_start"], dyn["cell_end"],
                                t_sta, window)
        )
        is_up &= ~in_cell.any(axis=1)
    sp = sim._phase_lookup(dyn["speed"], t_sta, period)
    beta_i = jnp.where(sp == 1.0, beta_x, a + (beta_x - a) / sp)
    lost = (drop | ~is_up) & sent
    return beta_i, lost


def _ge_step(bad, ge_params, u_trans, u_loss, sent):
    """Gilbert–Elliott: loss by the current state, then the per-packet state
    transition (the chain advances even for packets already lost to an
    outage — the radio fades regardless).  ``u_loss``/``sent`` may carry a
    leading tenant axis (fleet: one shared chain per helper, per-tenant
    loss draws); ``bad``/``u_trans`` stay (N,)."""
    p_bad, p_good, l_good, l_bad = ge_params
    lost = (u_loss < jnp.where(bad, l_bad, l_good)) & sent
    bad_next = jnp.where(bad, u_trans >= p_good, u_trans < p_bad)
    return lost, bad_next


def _transport_step(dyn, x, ge_bad):
    """Observation delay of this step's feedback (transport layer on):
    the sampled feedback RTT, doubled when the ACK is lost — composed
    with the same GE chain state that governs this step's data loss.
    ``ge_bad`` is None when the GE chain is off.  Broadcasts over a
    leading tenant axis in ``x`` (the fleet scan)."""
    return transport_mod.observation_delay(
        dyn["rtt_base"] * x["rtt_jit"], x["ack_u"], dyn["ack_p_drop"],
        ge_bad=ge_bad, ge_params=dyn.get("ge_params"))


def _send_time_ids(sym_next, tx, sent):
    """Send-time coded-symbol assignment: rank this step's sends by their
    send instant (ties -> helper index, i.e. the legacy round-robin order)
    and hand out the next unissued global ids in that order, so a slow
    helper never sits on an early systematic id while fast helpers burn
    parities.  Unsent slots consume nothing; their placeholder ids are
    never absorbed (received=False) and never finish (tr=inf), so they
    cannot enter a decode prefix."""
    order = jnp.argsort(jnp.where(sent, tx, jnp.inf))
    rank = jnp.argsort(order).astype(jnp.int32)
    return sym_next + rank, sym_next + sent.sum(dtype=jnp.int32)


def _decode_step(dec, t_hi, t_done, tables, ids, received, tr_ok):
    """Absorb this step's result arrivals into the peeling decoder and
    maintain the real-time decode bound: every absorbed result has arrived
    by ``t_hi``, so when ``done`` first fires the collector provably holds
    a decodable set by then (StepCtx doc)."""
    dec = decode_mod.absorb(dec, tables, ids, received)
    t_hi = jnp.maximum(t_hi, jnp.where(received, tr_ok, 0.0).max())
    t_done = jnp.where(dec["done"] & ~jnp.isfinite(t_done), t_hi, t_done)
    return dec, t_hi, t_done


def _hook_step(policy, pstate, ctx, churn: bool):
    """One policy-hook round: receipt handling, pacing, and — under churn —
    the loss reaction, applied as ``where(lost, tx_retx, tx_next)``."""
    pstate = policy.on_computed(pstate, ctx)
    tx_next = policy.next_load(pstate, ctx)
    if churn:
        pstate, tx_retx = policy.on_timeout(pstate, ctx, tx_next)
        tx_next = jnp.where(ctx.lost, tx_retx, tx_next)
    b = policy.backoff(pstate)
    return pstate, tx_next, b if b is not None else jnp.ones(ctx.n)


# ---------------------------------------------------------------------------
# The per-helper timeline scan (scenario dynamics x policy hooks)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("policy", "cfg_static", "churn_static")
)
def policy_stream(beta, d_up, d_ack, d_down, policy, cfg_static,
                  churn_static=None, dyn=None, a=None, aux=None):
    """Simulate M packets on every helper under ``policy``.

    Returns ``(outs, psummary)``: ``outs`` is the dict of (N, M) trace
    arrays (tr, idle, tx, arrive, beta, lost, backoff, and — for
    decoder-in-the-loop policies — ``sym_id``, the global coded id each
    send slot carried under the send-time assignment) plus ``tx_end``
    (N,) — the send time of the first unsimulated packet — and
    ``psummary`` is ``policy.summary(final_state)``.

    cfg_static: hashable (Bx, Br, Back, alpha) tuple.
    churn_static: ``ChurnConfig.static_key()`` — hashable (period,
        max_backoff, outage_dist, ge_enabled, cell_enabled, rtt_dist) —
        or the pre-transport 5-tuple / legacy (period, max_backoff)
        2-tuple (phase outages only), or None for the static paper
        model.  When set, ``dyn`` (from
        :func:`repro.core.simulator.draw_dynamics`) and ``a`` (N,)
        runtime offsets must be provided.  A ``rtt_dist != 'off'``
        switches on the transport feedback-delay line: the policy hooks
        then see *observed* instants (``ctx.tr_ok`` / ``ctx.rtt_ack`` /
        ``ctx.tr_prev`` shifted by the sampled feedback delay, and
        ``decode_t_done`` as a master-observed bound) while the returned
        trace stays physical (docs/transport.md).
    aux: ``policy.prepare()`` output (per-rep traced pytree).
    """
    Bx, Br, Back, alpha = cfg_static
    cfg = ccp_mod.CCPConfig(Bx=Bx, Br=Br, Back=Back, alpha=alpha)
    N, M = beta.shape
    aux = {} if aux is None else aux
    churn = churn_static is not None
    ge_on = cell_on = False
    outage_dist = "phase"
    rtt_dist = "off"
    max_backoff = None
    if churn:
        (period, max_backoff, outage_dist, ge_on,
         cell_on, rtt_dist) = _parse_churn_static(churn_static)
        window = period * dyn["speed"].shape[1]
    rtt_on = rtt_dist != "off"

    use_dec = bool(policy.uses_decoder)
    carry0 = dict(
        tx=jnp.zeros(N),              # send time of current packet (Tx_{n,1}=0)
        done_prev=jnp.zeros(N),
        tr_prev=jnp.zeros(N),
        pstate=policy.init(N),
    )
    if use_dec:
        # Incremental peeling decoder riding the scan carry: prepare() puts
        # the parity-pool tables + zero state under aux["decoder"].
        carry0["dec"] = aux["decoder"]["state0"]
        carry0["dec_t_hi"] = jnp.float32(0.0)   # max received tr so far
        carry0["dec_t_done"] = jnp.float32(jnp.inf)  # t_hi when done fired
        carry0["sym_next"] = jnp.int32(0)       # next unissued coded id
    xs = dict(
        beta=beta.T, d_up=d_up.T, d_ack=d_ack.T, d_down=d_down.T,
        i=jnp.arange(M),
    )
    if churn:
        xs["drop"] = dyn["drop"].T
    if ge_on:
        carry0["ge_bad"] = dyn["ge_bad0"]
        xs["ge_u_trans"] = dyn["ge_u_trans"].T
        xs["ge_u_loss"] = dyn["ge_u_loss"].T
    if rtt_on:
        xs["rtt_jit"] = dyn["rtt_jit"].T
        xs["ack_u"] = dyn["ack_u"].T

    def step(carry, x):
        tx = carry["tx"]
        # A policy may stop a helper's stream by emitting tx = +inf
        # (permanent: decoder-feedback policies stop once decode succeeds).
        # Unsent packets are non-events: no loss, no idle, no receipt —
        # churn lookups run on clamped times so no inf reaches an index op.
        sent = jnp.isfinite(tx)
        arrive = tx + x["d_up"]
        start = jnp.maximum(arrive, carry["done_prev"])
        t_arr = jnp.where(sent, arrive, 0.0)
        t_sta = jnp.where(sent, start, 0.0)
        if churn:
            beta_i, lost = _churn_step(
                dyn, a, x["beta"], x["drop"], t_arr, t_sta, sent,
                period=period, window=window, outage_dist=outage_dist,
                cell_on=cell_on,
            )
        else:
            beta_i = x["beta"]
            lost = jnp.zeros((N,), bool)
        if ge_on:
            lost_ge, ge_bad_next = _ge_step(
                carry["ge_bad"], dyn["ge_params"], x["ge_u_trans"],
                x["ge_u_loss"], sent,
            )
            lost |= lost_ge
        received = ~lost & sent
        done_ok = start + beta_i
        tr_ok = done_ok + x["d_down"]
        # A lost packet never occupies the helper nor reaches the collector.
        done = jnp.where(lost, carry["done_prev"], done_ok)
        tr = jnp.where(received, tr_ok, jnp.inf)
        idle = jnp.where(
            received, jnp.maximum(arrive - carry["done_prev"], 0.0), 0.0
        )
        rtt_ack = x["d_up"] + x["d_ack"]

        # Transport delay line (docs/transport.md): the physics above is
        # final — what follows (decoder absorb, policy hooks) runs on the
        # *observed* instants, one feedback RTT late (two when the ACK was
        # lost and NACK-retransmitted).  At rtt_mean = 0 the delay is
        # exactly 0.0, so the enabled path is bitwise the idealized scan.
        if rtt_on:
            obs_delay = _transport_step(
                dyn, x, carry["ge_bad"] if ge_on else None)
            tr_obs = tr_ok + obs_delay
            rtt_obs = rtt_ack + obs_delay
        else:
            tr_obs, rtt_obs = tr_ok, rtt_ack

        if use_dec:
            # Absorb this step's result arrivals into the peeling decoder
            # before the hooks run: the feedback a policy sees at step i is
            # everything an eagerly-decoding collector has recovered from
            # packets 0..i (see docs/policies.md for the causality note).
            # Fresh coded ids are handed out in send-time order, so early
            # (systematic) ids go to the helpers that actually send early.
            ids, sym_next = _send_time_ids(carry["sym_next"], tx, sent)
            # tr_obs, not tr_ok: decode_t_done is the master-*observed*
            # bound — the instant the controller can know the collector
            # holds a decodable set, which under transport lags the
            # physical decode by the feedback delay of the closing packet.
            dec, t_hi, t_done = _decode_step(
                carry["dec"], carry["dec_t_hi"], carry["dec_t_done"],
                aux["decoder"]["tables"], ids, received, tr_obs,
            )
            dec_kw = dict(decoded_count=dec["count"], ripple=dec["ripple"],
                          decode_done=dec["done"], decode_t_done=t_done)
        else:
            dec = None
            dec_kw = {}

        ctx = policies_mod.StepCtx(
            i=x["i"], n=N, tx=tx, arrive=arrive, start=start, beta=beta_i,
            tr_ok=tr_obs, lost=lost, received=received, rtt_ack=rtt_obs,
            d_up=x["d_up"], d_down=x["d_down"], d_ack=x["d_ack"],
            tr_prev=carry["tr_prev"], cfg=cfg, max_backoff=max_backoff,
            aux=aux, **dec_kw,
        )
        pstate, tx_next, b = _hook_step(policy, carry["pstate"], ctx, churn)

        new_carry = dict(
            tx=tx_next, done_prev=done,
            tr_prev=jnp.where(received, tr_obs, carry["tr_prev"]),
            pstate=pstate,
        )
        if ge_on:
            new_carry["ge_bad"] = ge_bad_next
        if use_dec:
            new_carry["dec"] = dec
            new_carry["dec_t_hi"] = t_hi
            new_carry["dec_t_done"] = t_done
            new_carry["sym_next"] = sym_next
        out = dict(tr=tr, idle=idle, tx=tx, arrive=arrive,
                   beta=jnp.where(sent, beta_i, 0.0), lost=lost,
                   backoff=b)
        if use_dec:
            out["sym_id"] = ids
        return new_carry, out

    final, outs = jax.lax.scan(step, carry0, xs)
    res = {k: v.T for k, v in outs.items()}  # (N, M)
    res["tx_end"] = final["tx"]
    psum = policy.summary(final["pstate"])
    if use_dec:
        # Surface the end-of-horizon decoder state next to the policy's own
        # summary scalars (-> RunResult.extras dec_count / dec_done).
        psum = dict(psum, dec_count=final["dec"]["count"],
                    dec_done=final["dec"]["done"])
    return res, psum


# ---------------------------------------------------------------------------
# One Monte-Carlo rep (pure-jax core shared by the sequential, vmapped and
# sharded runners)
# ---------------------------------------------------------------------------

def _sim_one(key, cfg, R: int, M: int, policy) -> Dict[str, jnp.ndarray]:
    """Full single-rep pipeline as a traceable function of ``key``."""
    k_h, k_p = jax.random.split(key)
    mu, a, rate = sim.draw_helpers(k_h, cfg)
    beta, d_up, d_ack, d_down = sim.draw_packet_tables(
        k_p, cfg, mu, a, rate, M, R)
    c = cfg.ccp_cfg(R)
    cfg_static = (c.Bx, c.Br, c.Back, c.alpha)
    aux = policy.prepare(cfg, R, c, mu, a, rate)
    if cfg.churn is None:
        outs, psum = policy_stream(beta, d_up, d_ack, d_down, policy=policy,
                                   cfg_static=cfg_static, aux=aux)
        tx_end = None
    else:
        k_c = jax.random.fold_in(key, 0xC0DE)
        dyn = sim.draw_dynamics(k_c, cfg, M)
        outs, psum = policy_stream(
            beta, d_up, d_ack, d_down, policy=policy, cfg_static=cfg_static,
            churn_static=cfg.churn.static_key(), dyn=dyn, a=a, aux=aux,
        )
        tx_end = outs["tx_end"]
    kk = R + cfg.K(R)
    t, valid = policy.finalize(outs, aux, cfg, R, kk, tx_end)
    mask = policy.packet_mask(aux, cfg.N, M)
    if mask is None:
        tr_eff, idle_eff, beta_eff = outs["tr"], outs["idle"], outs["beta"]
    else:
        # Block policies: packets beyond the assigned block do not exist
        # physically — exclude them from the per-helper statistics.
        tr_eff = jnp.where(mask, outs["tr"], jnp.inf)
        idle_eff = jnp.where(mask, outs["idle"], 0.0)
        beta_eff = jnp.where(mask, outs["beta"], 0.0)
    eff = sim.efficiency_measured(tr_eff, idle_eff, beta_eff, t)
    # isfinite guard: when t is +inf (an uncompletable block-policy rep)
    # the inf sentinels in tr_eff must not count as delivered packets.
    r_n = (jnp.isfinite(tr_eff) & (tr_eff <= t)).sum(axis=1)
    max_backoff = outs["backoff"].max(axis=1)
    # Loss rate over packets actually *sent*: a decoder-feedback policy that
    # stops a stream early must not have its never-sent tail slots (lost =
    # False by construction) dilute the reported rate.  Expressed as a
    # rescale of mean() so always-sending policies (n_sent == M, scale
    # exactly 1.0) stay bit-identical to the pre-PR-4 goldens.
    n_sent = jnp.isfinite(outs["tx"]).sum(axis=1)
    m_steps = outs["lost"].shape[1]
    lost_frac = outs["lost"].mean(axis=1) * (
        m_steps / jnp.maximum(n_sent, 1))
    res = dict(T=t, valid=valid, efficiency=eff, r_n=r_n, mu=mu, a=a,
               rate=rate, max_backoff=max_backoff, lost_frac=lost_frac)
    for k in getattr(policy, "report_aux", ()):
        res[f"x_{k}"] = aux[k]
    for k, v in psum.items():
        res[f"x_{k}"] = v
    return res


# ---------------------------------------------------------------------------
# One fleet Monte-Carlo rep: Tt tenants contending for cfg.N shared helpers
# through the event-clock scan (repro.core.fleet.stream).
# ---------------------------------------------------------------------------

def _fleet_one(key, cfg, R: int, M: int, policy, fleet) -> Dict[str, jnp.ndarray]:
    """Full single-rep fleet pipeline as a traceable function of ``key``.

    Mirrors ``_sim_one`` with a leading task axis: the helper draw (and the
    helper-state churn processes) are shared — the fleet contends for ONE
    pool — while packet tables and per-packet loss draws are per tenant.
    Task 0 reuses the single-task draws bit-for-bit (the equivalence spine).
    """
    from . import fleet as fleet_mod  # deferred: fleet imports the kernels above

    k_h, k_p = jax.random.split(key)
    mu, a, rate = sim.draw_helpers(k_h, cfg)
    Tt = fleet.n_tasks
    beta, d_up, d_ack, d_down = sim.draw_packet_tables_fleet(
        k_p, cfg, mu, a, rate, Tt, M, R)
    c = cfg.ccp_cfg(R)
    cfg_static = (c.Bx, c.Br, c.Back, c.alpha)
    release = fleet_mod.draw_releases(jax.random.fold_in(key, 0xF7EE), fleet)
    recruit, prio = fleet_mod.place(
        jax.random.fold_in(key, 0xAD31), fleet, cfg, mu, a, rate)
    per_task_aux = policy.fleet_aux == "per_task"
    if per_task_aux:
        # Block policies: one aux per tenant so the fixed allocation
        # lands on the tenant's recruited helpers (see Policy.prepare_fleet)
        aux = policy.prepare_fleet(cfg, R, c, mu, a, rate, recruit)
    else:
        aux = policy.prepare(cfg, R, c, mu, a, rate)
    if cfg.churn is None:
        outs, psum = fleet_mod.fleet_stream(
            beta, d_up, d_ack, d_down, release, recruit, prio,
            policy=policy, cfg_static=cfg_static,
            fleet_static=fleet.static_key(), aux=aux,
            aux_task_axis=per_task_aux)
        tx_end = None
    else:
        dyn = sim.draw_dynamics_fleet(
            jax.random.fold_in(key, 0xC0DE), cfg, M, Tt)
        outs, psum = fleet_mod.fleet_stream(
            beta, d_up, d_ack, d_down, release, recruit, prio,
            policy=policy, cfg_static=cfg_static,
            fleet_static=fleet.static_key(),
            churn_static=cfg.churn.static_key(), dyn=dyn, a=a, aux=aux,
            aux_task_axis=per_task_aux)
        tx_end = outs["tx_end"]
    kk = R + cfg.K(R)
    if per_task_aux:
        mask = jax.vmap(lambda at: policy.packet_mask(at, cfg.N, M))(aux)
    else:
        mask = policy.packet_mask(aux, cfg.N, M)
    per_keys = ("tr", "idle", "tx", "arrive", "beta", "lost", "backoff")
    if policy.uses_decoder:
        per_keys += ("sym_id",)
    task_outs = {k: outs[k] for k in per_keys}

    def _finish(outs_t, tx_end_t, aux_t, mask_t):
        # Per-task completion + per-helper statistics: the same extraction
        # as _sim_one, vmapped over the task axis (aux/mask mapped per
        # task for fleet_aux == "per_task" block policies, else shared).
        t, valid = policy.finalize(outs_t, aux_t, cfg, R, kk, tx_end_t)
        if mask_t is None:
            tr_eff, idle_eff, beta_eff = (
                outs_t["tr"], outs_t["idle"], outs_t["beta"])
        else:
            tr_eff = jnp.where(mask_t, outs_t["tr"], jnp.inf)
            idle_eff = jnp.where(mask_t, outs_t["idle"], 0.0)
            beta_eff = jnp.where(mask_t, outs_t["beta"], 0.0)
        eff = sim.efficiency_measured(tr_eff, idle_eff, beta_eff, t)
        r_n = (jnp.isfinite(tr_eff) & (tr_eff <= t)).sum(axis=1)
        n_sent = jnp.isfinite(outs_t["tx"]).sum(axis=1)
        m_steps = outs_t["lost"].shape[1]
        lost_frac = outs_t["lost"].mean(axis=1) * (
            m_steps / jnp.maximum(n_sent, 1))
        return dict(T=t, valid=valid, efficiency=eff, r_n=r_n,
                    max_backoff=outs_t["backoff"].max(axis=1),
                    lost_frac=lost_frac)

    aux_ax = 0 if per_task_aux else None
    if tx_end is None:
        res = jax.vmap(lambda o, at, mt: _finish(o, None, at, mt),
                       in_axes=(0, aux_ax, aux_ax))(task_outs, aux, mask)
    else:
        res = jax.vmap(_finish, in_axes=(0, 0, aux_ax, aux_ax))(
            task_outs, tx_end, aux, mask)
    res["release"] = release
    res["sojourn"] = res["T"] - release
    # Fleet-level metrics: helper utilization over the rep's makespan and
    # Jain fairness over the valid tenants' sojourn times.
    vmask = res["valid"] & jnp.isfinite(res["T"])
    makespan = jnp.max(jnp.where(vmask, res["T"], -jnp.inf))
    res["makespan"] = makespan
    res["util"] = fleet_mod.helper_utilization(
        outs["beta"], outs["tr"], d_down, makespan)
    res["fairness"] = fleet_mod.jain_fairness(res["sojourn"], vmask)
    res.update(mu=mu, a=a, rate=rate)
    for k in getattr(policy, "report_aux", ()):
        res[f"x_{k}"] = aux[k]
    for k, v in psum.items():
        res[f"x_{k}"] = v
    return res


@functools.partial(jax.jit, static_argnames=("cfg", "R", "M", "policy"))
def _sim_one_jit(key, cfg, R, M, policy):
    return _sim_one(key, cfg, R, M, policy)


@functools.partial(
    jax.jit, static_argnames=("cfg", "R", "M", "policy", "fleet")
)
def _fleet_batch_jit(keys, cfg, R, M, policy, fleet):
    return jax.vmap(lambda k: _fleet_one(k, cfg, R, M, policy, fleet))(keys)


@functools.partial(jax.jit, static_argnames=("cfg", "R", "M", "policy"))
def _sim_batch_jit(keys, cfg, R, M, policy):
    return jax.vmap(lambda k: _sim_one(k, cfg, R, M, policy))(keys)


@functools.lru_cache(maxsize=None)
def _sharded_batch_fn(cfg, R: int, M: int, policy, devs: tuple, batch: int):
    """Jitted shard_map runner: the key batch is split over a 1-D 'data'
    mesh of ``devs`` and each device vmaps its shard through ``_sim_one``
    — per-rep lanes are independent, so no collectives and results are
    identical to the single-device vmap."""
    from jax.sharding import PartitionSpec

    from ..parallel import sharding as shd

    mesh = shd.data_mesh(devs)
    spec = shd.batch_spec(mesh, batch, extra_dims=1)
    body = lambda k: jax.vmap(lambda kk: _sim_one(kk, cfg, R, M, policy))(k)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                       out_specs=PartitionSpec("data"), check_vma=False)
    return jax.jit(fn)


def _sim_batch_sharded(keys, cfg, R: int, M: int, policy, devices=None):
    """Device-sharded batch: pad the key batch to a multiple of the device
    count (padding reps are discarded after the run) and shard it over the
    local device mesh."""
    devs = tuple(devices) if devices is not None else tuple(jax.local_devices())
    B = keys.shape[0]
    pad = (-B) % len(devs)
    keys_p = keys if pad == 0 else jnp.concatenate(
        [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])]
    )
    out = _sharded_batch_fn(cfg, R, M, policy, devs, keys_p.shape[0])(keys_p)
    return {k: v[:B] for k, v in out.items()}


@functools.lru_cache(maxsize=None)
def _fleet_sharded_batch_fn(cfg, R: int, M: int, policy, fleet, devs: tuple,
                            batch: int):
    """Fleet twin of :func:`_sharded_batch_fn`: the key batch splits over
    the same 1-D 'data' mesh and each device vmaps its shard through
    ``_fleet_one``.  Reps are independent (every tenant of a rep lives on
    that rep's device), so there are no collectives and the sharded run
    is bitwise the single-device ``_fleet_batch_jit`` vmap."""
    from jax.sharding import PartitionSpec

    from ..parallel import sharding as shd

    mesh = shd.data_mesh(devs)
    spec = shd.batch_spec(mesh, batch, extra_dims=1)
    body = lambda k: jax.vmap(
        lambda kk: _fleet_one(kk, cfg, R, M, policy, fleet))(k)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,),
                       out_specs=PartitionSpec("data"), check_vma=False)
    return jax.jit(fn)


def _fleet_batch_sharded(keys, cfg, R: int, M: int, policy, fleet,
                         devices=None):
    """Device-sharded fleet batch (pad-to-device-multiple, as in
    :func:`_sim_batch_sharded`)."""
    devs = tuple(devices) if devices is not None else tuple(jax.local_devices())
    B = keys.shape[0]
    pad = (-B) % len(devs)
    keys_p = keys if pad == 0 else jnp.concatenate(
        [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])]
    )
    out = _fleet_sharded_batch_fn(
        cfg, R, M, policy, fleet, devs, keys_p.shape[0])(keys_p)
    return {k: v[:B] for k, v in out.items()}


def _m_cap(cfg, kk: int, policy) -> int:
    # Static: every helper streams back-to-back, so M = R+K always
    # certifies.  Under churn a helper's M packets can include losses;
    # block policies must cover the largest assigned block — leave headroom.
    factor = policy.m_cap_factor
    if factor is None:
        factor = 1 if cfg.churn is None else 4
    return factor * kk


def _initial_m(base_m: int, cfg, R: int, kk: int, cap: int, policy,
               M_override: Optional[int]) -> int:
    """Starting horizon shared by the batched and sequential runners: the
    engine heuristic ``base_m``, clamped by the policy's ``horizon_hint``
    (block policies: ~R/N packets) and the cap.  Certification doubling
    backstops a hint that guessed low."""
    if M_override is not None:
        return min(M_override, cap)
    m = base_m
    hint = policy.horizon_hint(cfg, R, kk)
    if hint is not None:
        m = min(m, max(int(hint), 32))
    return min(m, cap)


# ---------------------------------------------------------------------------
# RunResult + Engine
# ---------------------------------------------------------------------------

_CORE_FIELDS = ("T", "valid", "efficiency", "r_n", "mu", "a", "rate",
                "max_backoff", "lost_frac")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=list(_CORE_FIELDS) + ["extras"],
    meta_fields=["M", "policy"],
)
@dataclasses.dataclass
class RunResult:
    """Structured result of ``Engine.run`` over a key batch of B reps.

    T (B,) completion times; valid (B,) certification mask (False: the
    horizon cap was hit before the completion time could be certified —
    the rep MUST be dropped and counted, never averaged); efficiency /
    r_n / mu / a / rate / max_backoff / lost_frac (B, N) per-helper
    statistics; M the shared horizon actually used; policy the registry
    name; extras the policy trace (e.g. ``loads`` for the block
    baselines, ``p_hat`` for ``adaptive_rate``).
    """

    T: np.ndarray
    valid: np.ndarray
    efficiency: np.ndarray
    r_n: np.ndarray
    mu: np.ndarray
    a: np.ndarray
    rate: np.ndarray
    max_backoff: np.ndarray
    lost_frac: np.ndarray
    extras: Dict[str, np.ndarray]
    M: int
    policy: str

    # dict-style access keeps dict-shaped consumers (the shared benchmark
    # helpers) working on either representation.
    def __getitem__(self, key):
        d = self.as_dict()
        return d[key]

    def keys(self):
        return self.as_dict().keys()

    def as_dict(self) -> Dict[str, np.ndarray]:
        d = {f: getattr(self, f) for f in _CORE_FIELDS}
        d.update(self.extras)
        d["M"] = self.M
        return d


_FLEET_FIELDS = ("T", "sojourn", "release", "valid", "efficiency", "r_n",
                 "mu", "a", "rate", "max_backoff", "lost_frac", "util",
                 "fairness", "makespan")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=list(_FLEET_FIELDS) + ["extras"],
    meta_fields=["M", "policy", "n_tasks", "discipline"],
)
@dataclasses.dataclass
class FleetRunResult:
    """Structured result of ``Engine.run_fleet`` over B reps of a
    ``n_tasks``-tenant fleet sharing ``cfg.N`` helpers.

    T / sojourn / release / valid: (B, n_tasks) per-task completion time
    (absolute), completion minus release, release time, and certification
    mask (an uncertified task MUST be dropped and counted, never averaged);
    efficiency / r_n / max_backoff / lost_frac: (B, n_tasks, N) per-task
    per-helper statistics; mu / a / rate: (B, N) shared helper draws; util:
    (B, N) per-helper busy fraction inside the rep's makespan; fairness:
    (B,) Jain index over the valid tasks' sojourns; makespan: (B,) last
    valid completion.  ``summary()`` reduces the batch to the scalars the
    ``fig_fleet`` sweep plots.
    """

    T: np.ndarray
    sojourn: np.ndarray
    release: np.ndarray
    valid: np.ndarray
    efficiency: np.ndarray
    r_n: np.ndarray
    mu: np.ndarray
    a: np.ndarray
    rate: np.ndarray
    max_backoff: np.ndarray
    lost_frac: np.ndarray
    util: np.ndarray
    fairness: np.ndarray
    makespan: np.ndarray
    extras: Dict[str, np.ndarray]
    M: int
    policy: str
    n_tasks: int
    discipline: str

    def __getitem__(self, key):
        return self.as_dict()[key]

    def keys(self):
        return self.as_dict().keys()

    def as_dict(self) -> Dict[str, np.ndarray]:
        d = {f: getattr(self, f) for f in _FLEET_FIELDS}
        d.update(self.extras)
        d["M"] = self.M
        return d

    def summary(self) -> Dict[str, float]:
        """Batch scalars for the saturation sweep: p50/p99 sojourn over the
        certified tasks, mean helper utilization and fairness, and the
        uncertified-task count."""
        ok = np.asarray(self.valid, bool) & np.isfinite(self.sojourn)
        soj = np.asarray(self.sojourn)[ok]
        return dict(
            p50=float(np.percentile(soj, 50)) if soj.size else float("nan"),
            p99=float(np.percentile(soj, 99)) if soj.size else float("nan"),
            util_mean=float(np.nanmean(np.asarray(self.util))),
            fairness_mean=float(np.nanmean(np.asarray(self.fairness))),
            invalid=int((~np.asarray(self.valid, bool)).sum()),
        )


class Engine:
    """Single entry point for policy-driven Monte-Carlo simulation.

    ``Engine.run(cfg, policy, keys, R)`` vmaps the whole per-rep pipeline
    (helper draw -> packet tables -> policy-driven stream scan -> policy
    completion rule) over a batch of PRNG keys with one shared,
    power-of-two-bucketed horizon M and a single certification pass: if
    any rep is uncertified the shared horizon doubles and the whole batch
    re-runs (one extra compile, amortized across the sweep).  With
    ``shard=True`` the key batch is additionally split across the local
    devices through ``shard_map`` on a 1-D 'data' mesh (padded to a
    device-count multiple); per-rep lanes never communicate, so sharded
    results are bitwise identical to the unsharded vmap.
    """

    def __init__(self, shard: bool = False, devices=None):
        self.shard = shard
        self.devices = devices

    def run(self, cfg, policy, keys, R: int, *,
            M_override: Optional[int] = None,
            shard: Optional[bool] = None, devices=None) -> RunResult:
        """Run ``policy`` (a registry name or Policy instance) over a key
        batch; returns a :class:`RunResult`."""
        policy = _as_policy(policy)
        shard = self.shard if shard is None else shard
        devices = self.devices if devices is None else devices
        keys = _check_inputs(keys, R)
        kk = R + cfg.K(R)
        cap = _m_cap(cfg, kk, policy)
        M = _initial_m(sim._horizon_shared(cfg, R), cfg, R, kk, cap, policy,
                       M_override)
        for _ in range(8):
            if shard:
                out = _sim_batch_sharded(keys, cfg, R, M, policy, devices)
            else:
                out = _sim_batch_jit(keys, cfg, R, M, policy)
            if bool(out["valid"].all()) or M >= cap or M_override is not None:
                break
            M = min(M * 2, cap)
        res = {k: np.asarray(v) for k, v in out.items()}
        extras = {k[2:]: v for k, v in res.items() if k.startswith("x_")}
        core = {k: v for k, v in res.items() if not k.startswith("x_")}
        return RunResult(M=M, policy=policy.name, extras=extras, **core)

    def run_fleet(self, cfg, policy, keys, R: int, *, fleet=None,
                  M_override: Optional[int] = None,
                  shard: Optional[bool] = None,
                  devices=None) -> FleetRunResult:
        """Multi-tenant event-clock run: ``fleet.n_tasks`` concurrent tasks
        contend for the ``cfg.N`` shared helpers under the configured
        service discipline and admission rule (see docs/fleet.md).

        ``fleet`` is a :class:`repro.core.fleet.FleetConfig` (default: one
        task, all helpers, FIFO).  At ``n_tasks=1`` with the default
        all-helpers placement the event-clock scan is bit-for-bit
        ``Engine.run`` for every registered policy — the equivalence-spine
        tests in ``tests/test_fleet.py`` pin this against the goldens.
        Certification works as in :meth:`run`: the shared horizon doubles
        until every (rep, task) completion is certified or the cap is hit.
        With ``shard=True`` (or an ``Engine(shard=True)``) the key batch
        splits over the local 'data' mesh exactly as in :meth:`run`, and
        the sharded results are bitwise the vmap path's.
        """
        from . import fleet as fleet_mod

        policy = _as_policy(policy)
        shard = self.shard if shard is None else shard
        devices = self.devices if devices is None else devices
        fleet = fleet_mod.FleetConfig() if fleet is None else fleet
        if not isinstance(fleet, fleet_mod.FleetConfig):
            raise TypeError(
                "fleet must be a repro.core.fleet.FleetConfig (or None for "
                f"the 1-task default), got {type(fleet).__name__}: {fleet!r}"
            )
        if fleet.placement not in fleet_mod.PLACEMENTS:
            raise ValueError(
                f"unknown placement {fleet.placement!r}; known: "
                f"{sorted(fleet_mod.PLACEMENTS)} (register_placement adds "
                "custom rules)"
            )
        if (fleet.helpers_per_task is not None
                and fleet.helpers_per_task > cfg.N):
            raise ValueError(
                f"helpers_per_task={fleet.helpers_per_task} exceeds the "
                f"cfg.N={cfg.N} helpers in the pool"
            )
        keys = _check_inputs(keys, R)
        kk = R + cfg.K(R)
        cap = _m_cap(cfg, kk, policy)
        M = _initial_m(sim._horizon_shared(cfg, R), cfg, R, kk, cap, policy,
                       M_override)
        for _ in range(8):
            if shard:
                out = _fleet_batch_sharded(
                    keys, cfg, R, M, policy, fleet, devices)
            else:
                out = _fleet_batch_jit(keys, cfg, R, M, policy, fleet)
            if bool(out["valid"].all()) or M >= cap or M_override is not None:
                break
            M = min(M * 2, cap)
        res = {k: np.asarray(v) for k, v in out.items()}
        extras = {k[2:]: v for k, v in res.items() if k.startswith("x_")}
        core = {k: v for k, v in res.items() if not k.startswith("x_")}
        return FleetRunResult(M=M, policy=policy.name,
                              n_tasks=fleet.n_tasks,
                              discipline=fleet.discipline,
                              extras=extras, **core)

    def run_one(self, key, cfg, policy, R: int, *,
                M_override: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Sequential single-rep runner (grows the horizon per draw);
        mirrors the legacy ``simulator._run_mode`` contract."""
        policy = _as_policy(policy)
        if isinstance(R, bool) or not isinstance(R, (int, np.integer)) or R <= 0:
            raise ValueError(
                f"R must be a positive int (source packets per task), got {R!r}"
            )
        k_h, _ = jax.random.split(key)
        mu, a, _rate = sim.draw_helpers(k_h, cfg)
        kk = R + cfg.K(R)
        cap = _m_cap(cfg, kk, policy)
        M = _initial_m(sim._horizon(cfg, mu, a, R), cfg, R, kk, cap, policy,
                       M_override)
        for _ in range(8):  # grow horizon until completion is certified
            out = _sim_one_jit(key, cfg, R, M, policy)
            if bool(out["valid"]) or M >= cap or M_override is not None:
                break
            M = min(M * 2, cap)
        res = {k: np.asarray(v) for k, v in out.items()}
        res["T"] = float(res["T"])
        res["M"] = M
        return res
