"""GQA attention block: full/local (sliding-window), softcap, RoPE, KV cache."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import flash_attention
from ..kernels.flash_attention.ops import CHUNKED_THRESHOLD
from ..kernels.flash_attention.ref import attention_chunked, attention_ref
from .common import ParamBuilder, apply_rope
from .config import ModelConfig

# §Perf knob: when the KV cache is head_dim-sharded over 'model' (kv_heads
# don't divide the axis), contracting scores over the sharded head_dim makes
# GSPMD all-reduce (B,H,Tq,chunk)-sized SCORES (tens of GB at 32k).  Setting
# kv_gather to the batch axis name (or () for unsharded batch) constrains
# k/v to be gathered over 'model' before attention instead — an AG of the
# MB-scale cache slice per layer, with attention computed model-replicated.
ATTN_OPTS = {"kv_gather": None}


def set_attn_opts(kv_gather=None) -> None:
    ATTN_OPTS["kv_gather"] = kv_gather


def _maybe_gather_kv(ck, cv):
    spec = ATTN_OPTS["kv_gather"]
    if spec is None:
        return ck, cv
    from jax.sharding import PartitionSpec as P

    p = P(spec if spec else None, None, None, None)
    return (jax.lax.with_sharding_constraint(ck, p),
            jax.lax.with_sharding_constraint(cv, p))


def init_attention(pb: ParamBuilder, cfg: ModelConfig) -> Dict[str, Any]:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    p = {
        "wq": pb.fan_in((d, hq, hd), ("embed", "heads", "head_dim"), fan_axis=0),
        "wk": pb.fan_in((d, hkv, hd), ("embed", "kv_heads", "head_dim"), fan_axis=0),
        "wv": pb.fan_in((d, hkv, hd), ("embed", "kv_heads", "head_dim"), fan_axis=0),
        "wo": pb.fan_in((hq, hd, d), ("heads", "head_dim", "embed"), fan_axis=(0, 1)),
    }
    if cfg.attn_bias:
        p["bq"] = pb.zeros((hq, hd), ("heads", "head_dim"))
        p["bk"] = pb.zeros((hkv, hd), ("kv_heads", "head_dim"))
        p["bv"] = pb.zeros((hkv, hd), ("kv_heads", "head_dim"))
        p["bo"] = pb.zeros((d,), ("embed",))
    return p


def init_cross_attention(pb: ParamBuilder, cfg: ModelConfig) -> Dict[str, Any]:
    return init_attention(pb, cfg)


def _project(params, x, use_rope, positions, cfg):
    """x: (B, T, D) -> q (B,Hq,T,hd), k/v (B,Hkv,T,hd)."""
    q = jnp.einsum("btd,dhk->bhtk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bhtk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bhtk", x, params["wv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)[None, :, None, :]
        k = k + params["bk"].astype(x.dtype)[None, :, None, :]
        v = v + params["bv"].astype(x.dtype)[None, :, None, :]
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _out(params, o):
    """(B, Hq, T, hd) -> (B, T, D)."""
    y = jnp.einsum("bhtk,hkd->btd", o, params["wo"].astype(o.dtype))
    if "bo" in params:
        y = y + params["bo"].astype(o.dtype)
    return y


def attention(
    params: Dict[str, Any],
    x: jnp.ndarray,                       # (B, T, D)
    cfg: ModelConfig,
    *,
    local: bool = False,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    use_rope: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self-attention with optional KV cache.

    cache: {"k": (B,Hkv,Tmax,hd), "v": ..., "pos": scalar int32} — decode
    appends at ``pos`` and attends over the valid prefix.  Returns (y, cache').
    """
    B, T, _ = x.shape
    window = cfg.window if local else None
    if positions is None:
        base = 0 if cache is None else cache["pos"]
        positions = base + jnp.arange(T)[None, :]
        positions = jnp.broadcast_to(positions, (B, T))
    q, k, v = _project(params, x, use_rope, positions, cfg)

    if cache is not None and "ring" in cache:
        # Bounded ring buffer for local (sliding-window) layers: the buffer
        # holds exactly the last `window` tokens, so a 500k-token decode
        # reads O(window) KV instead of O(context) — recurrentgemma's
        # bounded-memory property realized in the cache layout.
        if T != 1:
            raise ValueError("ring caches support decode (T=1) only")
        pos = cache["pos"]
        wbuf = cache["k"].shape[2]
        slot = pos % wbuf
        ck = _dyn_update(jnp.asarray(cache["k"], k.dtype), k, slot)
        cv = _dyn_update(jnp.asarray(cache["v"], v.dtype), v, slot)
        new_cache = {"k": ck, "v": cv, "pos": pos + 1, "ring": cache["ring"]}
        valid = jnp.minimum(pos + 1, wbuf)
        # every stored token is within the window of the current query and
        # in its past — plain masked attention over the valid slots.
        o = attention_ref(
            q, ck, cv, causal=False, window=None, softcap=cfg.attn_softcap,
            q_offset=0, kv_len=jnp.full((B,), valid, jnp.int32),
        )
        return _out(params, o), new_cache

    if cache is not None:
        pos = cache["pos"]
        ck = jnp.asarray(cache["k"], k.dtype)
        cv = jnp.asarray(cache["v"], v.dtype)
        ck = _dyn_update(ck, k, pos)
        cv = _dyn_update(cv, v, pos)
        new_cache = {"k": ck, "v": cv, "pos": pos + T}
        ck, cv = _maybe_gather_kv(ck, cv)
        kv_len = pos + T
        # mask out beyond kv_len via big-negative trick inside ref path
        o = _attend_cached(
            q, ck, cv, kv_len, pos, cfg, window=window, causal=causal,
            use_pallas=use_pallas, interpret=interpret,
        )
        return _out(params, o), new_cache

    o = flash_attention(
        q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap,
        use_pallas=use_pallas, interpret=interpret,
    )
    return _out(params, o), None


def _dyn_update(cache: jnp.ndarray, new: jnp.ndarray, pos) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(cache, new, (0, 0, pos, 0))


def _attend_cached(q, ck, cv, kv_len, q_offset, cfg, *, window, causal,
                   use_pallas, interpret):
    """Attention against the cache with a dynamic valid length.

    The kernel path requires static lengths; for decode we attend over the
    whole cache buffer with masking by position (padding keys are zeros but
    masked out by the kv_len comparison inside the reference / the causal
    frontier in the kernel).
    """
    B = q.shape[0]
    kv_len_vec = jnp.full((B,), kv_len, jnp.int32)
    q_pos = q_offset  # scalar traced offset
    # Reference paths support traced offsets/lengths; the Pallas kernel wants
    # static offsets, so serving uses the jnp paths (chunked for long caches
    # — O(T*chunk) memory instead of a (T_cache)^2 / B*H*T score blowup).
    if ck.shape[2] > CHUNKED_THRESHOLD:
        from ..kernels.flash_attention.ops import CHUNK_OPTS

        return attention_chunked(
            q, ck, cv, causal=causal, window=window, softcap=cfg.attn_softcap,
            q_offset=q_pos, kv_len=kv_len_vec, **CHUNK_OPTS,
        )
    return attention_ref(
        q, ck, cv, causal=causal, window=window, softcap=cfg.attn_softcap,
        q_offset=q_pos, kv_len=kv_len_vec,
    )


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, jnp.ndarray]:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, hkv, max_len, hd), dtype),
        "v": jnp.zeros((batch, hkv, max_len, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def cross_attention(
    params: Dict[str, Any],
    x: jnp.ndarray,            # (B, Tq, D) decoder states
    enc: jnp.ndarray,          # (B, Tk, D) encoder output
    cfg: ModelConfig,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    kv: Optional[Dict[str, jnp.ndarray]] = None,  # precomputed {"k","v"}
) -> jnp.ndarray:
    q = jnp.einsum("btd,dhk->bhtk", x, params["wq"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)[None, :, None, :]
    if kv is not None:
        k, v = kv["k"].astype(x.dtype), kv["v"].astype(x.dtype)
    else:
        k = jnp.einsum("btd,dhk->bhtk", enc, params["wk"].astype(enc.dtype))
        v = jnp.einsum("btd,dhk->bhtk", enc, params["wv"].astype(enc.dtype))
        if "bk" in params:
            k = k + params["bk"].astype(k.dtype)[None, :, None, :]
            v = v + params["bv"].astype(v.dtype)[None, :, None, :]
    o = flash_attention(
        q, k, v, causal=False, use_pallas=use_pallas, interpret=interpret
    )
    return _out(params, o)
