"""Mixture-of-Experts block with sort-based (dropping, capacity-bounded)
token dispatch.

Dispatch strategy (static shapes, EP-shardable, no (S, E, C) one-hot blowup):

  1. router scores -> top_k expert ids + weights per token;
  2. flatten the S*k assignments, sort by expert id;
  3. each expert e gets a static (C,) slot table: slot (e, c) holds the c-th
     token assigned to e (or -1 beyond its count — capacity drop, standard
     GShard semantics);
  4. gather -> (E, C, d), batched expert FFN einsum, scatter-add back with
     router weights.

The expert tensors carry the 'experts' logical axis, which the sharding
rules map to the 'model' mesh axis (expert parallelism); GSPMD turns the
gather/scatter into all-to-all collectives over that axis.  Parity blocks
for coded gradient aggregation stay *within* expert shards (DESIGN.md §4).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ACT, ParamBuilder
from .config import ModelConfig

# §Perf knobs (set by the dry-run/perf harness):
#   constrain — pin dispatched intermediates to EP sharding (GSPMD hint;
#     measured a no-op on qwen3, kept for the record — §Perf A1/A4);
#   a2a_mesh — use the explicit shard_map formulation in moe_a2a.py (the
#     measured fix for the dispatch-collective blowup — §Perf A5).
# Off by default: the baseline records the unconstrained partitioner.
MOE_OPTS = {"constrain": False, "a2a_mesh": None}


def set_moe_opts(constrain: bool = False, a2a_mesh=None) -> None:
    MOE_OPTS["constrain"] = constrain
    MOE_OPTS["a2a_mesh"] = a2a_mesh


def _constrain(x, spec):
    if not MOE_OPTS["constrain"]:
        return x
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(*spec))


def init_moe(pb: ParamBuilder, cfg: ModelConfig) -> Dict[str, Any]:
    assert cfg.moe is not None
    d, m = cfg.d_model, cfg.moe
    f = m.d_ff_expert
    p = {
        "router": pb.normal((d, m.n_experts), ("embed", "experts"), stddev=d ** -0.5),
        "w_gate": pb.fan_in((m.n_experts, d, f), ("experts", "embed", "ff"), fan_axis=1),
        "w_up": pb.fan_in((m.n_experts, d, f), ("experts", "embed", "ff"), fan_axis=1),
        "w_down": pb.fan_in((m.n_experts, f, d), ("experts", "ff", "embed"), fan_axis=1),
    }
    if m.n_shared:
        p["shared_gate"] = pb.fan_in((d, m.n_shared * f), ("embed", "ff"), fan_axis=0)
        p["shared_up"] = pb.fan_in((d, m.n_shared * f), ("embed", "ff"), fan_axis=0)
        p["shared_down"] = pb.fan_in((m.n_shared * f, d), ("ff", "embed"), fan_axis=0)
    return p


def _capacity(s_tokens: int, m) -> int:
    c = int(s_tokens * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(c, m.top_k)


def moe_block(
    params: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, D) -> (out, aux_loss). Aux = load-balance loss (Switch)."""
    if MOE_OPTS["a2a_mesh"] is not None:
        from .moe_a2a import moe_block_a2a

        return moe_block_a2a(params, x, cfg, MOE_OPTS["a2a_mesh"])
    m = cfg.moe
    B, T, D = x.shape
    S = B * T
    xf = x.reshape(S, D)
    logits = (xf @ params["router"].astype(x.dtype)).astype(jnp.float32)  # (S, E)
    if m.router_softcap:
        logits = m.router_softcap * jnp.tanh(logits / m.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)                # (S, k)
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- sort-based dispatch -------------------------------------------
    C = _capacity(S, m)
    flat_e = top_e.reshape(-1)                                   # (S*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(S), m.top_k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_w = flat_w[order]
    counts = jnp.bincount(flat_e, length=m.n_experts)            # (E,)
    offsets = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    # slot (e, c) -> index into sorted arrays, masked past each count
    slot_idx = offsets[:, None] + jnp.arange(C)[None, :]         # (E, C)
    slot_valid = jnp.arange(C)[None, :] < counts[:, None]
    slot_idx = jnp.clip(slot_idx, 0, S * m.top_k - 1)
    tok_at_slot = jnp.where(slot_valid, sorted_tok[slot_idx], 0)
    w_at_slot = jnp.where(slot_valid, sorted_w[slot_idx], 0.0)

    xd = xf[tok_at_slot]                                         # (E, C, D)
    xd = xd * slot_valid[..., None].astype(xd.dtype)
    xd = _constrain(xd, ("model", None, None))      # tokens move to experts
    act = ACT["silu"]
    g = act(jnp.einsum("ecd,edf->ecf", xd, params["w_gate"].astype(xd.dtype)))
    u = jnp.einsum("ecd,edf->ecf", xd, params["w_up"].astype(xd.dtype))
    g = _constrain(g, ("model", None, "data"))      # ff stays data-sharded
    u = _constrain(u, ("model", None, "data"))
    y = jnp.einsum("ecf,efd->ecd", g * u, params["w_down"].astype(xd.dtype))
    y = _constrain(y, ("model", None, None))        # psum over data inside
    y = y * w_at_slot[..., None].astype(y.dtype)

    out = jax.ops.segment_sum(
        y.reshape(-1, D).astype(x.dtype), tok_at_slot.reshape(-1),
        num_segments=S,
    ).astype(x.dtype)
    # data-sharded combine output: lets the partitioner reduce-scatter the
    # cross-(model,data) combine instead of all-reducing the full buffer
    out = _constrain(out, ("data", None))

    if m.n_shared:
        gs = act(xf @ params["shared_gate"].astype(x.dtype))
        us = xf @ params["shared_up"].astype(x.dtype)
        out = out + (gs * us) @ params["shared_down"].astype(x.dtype)

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.bincount(flat_e, length=m.n_experts) / (S * m.top_k)
    aux = m.n_experts * jnp.sum(me * ce)
    return out.reshape(B, T, D), aux.astype(jnp.float32)
