"""Plain reference of one chip's share of a Moonlight-16B-A3B MoE layer.

DeepSeek-V3's MoE layer (arXiv:2412.19437 §2.1.2, eq. 12-16) as the
published gate computes it (HF ``DeepseekV3MoEGate``: ``scoring_func``
sigmoid, ``topk_method`` noaux_tc, ``n_group`` = ``topk_group`` = 1,
``norm_topk_prob``, ``routed_scaling_factor``), in float32 at full
precision (``Precision.HIGHEST``), with no coding, kernels, dispatch or
padding:

  s_{i,t} = sigmoid(u_t . e_i)
  chosen  = TopK_i(s_{i,t} + b_i, K)                 (b: selection only)
  g_{i,t} = s_{i,t} / (sum_{j in chosen} s_{j,t} + 1e-20) * scale, i chosen
  h_t     = sum_{shared} FFN_s(u_t) + sum_i g_{i,t} FFN_i(u_t)
  FFN(u)  = W_down (silu(W_gate u) * (W_up u))

Every held expert runs on every token, weighted by the token's gate value
(zero where it did not choose the expert).  A share holds the routed
experts [first, first + held) and runs the shared experts for its own
slice of the tokens only; the residual is left out.  ``columns`` lays
out a product's columns as the coded layer's buffer holds them, and
``expert_columns`` and ``coded`` give the coded blocks' products G (W x)
of columns that each belong to one expert, ``fp8`` the control's
rounding.  It imports nothing of the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def gate(x, router, bias, top_k: int, scale: float):
    """(weights (T, k), ids (T, k)) of the experts each column of x
    (d, T) chooses."""
    s = jax.nn.sigmoid(_mm(x.astype(jnp.float32).T,
                           router.astype(jnp.float32).T))
    _, ids = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, ids, axis=1)
    return w / (w.sum(1, keepdims=True) + 1e-20) * scale, ids


def ffn(x, w_gate, w_up, w_down):
    """SwiGLU of the columns of x (d, n) -> (d, n)."""
    return _mm(w_down, jax.nn.silu(_mm(w_gate, x)) * _mm(w_up, x))


def layer(x, router, bias, gate_w, up_w, down_w, shared_gate, shared_up,
          shared_down, *, first: int, top_k: int, scale: float,
          shared_tokens: slice, cast=None):
    """The share's output (d, T) in float32 and the chosen ids (T, k).

    ``gate_w``/``up_w`` (held, f, d) and ``down_w`` (held, d, f) are the
    routed experts [first, first + held); ``shared_*`` the shared experts
    as one SwiGLU, (n_shared f, d) and (d, n_shared f).  ``cast`` rounds
    the experts' operands (weights, input and hidden activation), as the
    control does; the router stays float32, as the configuration states."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    cast = cast or (lambda a: a)
    x = f32(x)
    w, ids = gate(x, router, f32(bias), top_k, scale)
    xe = cast(x)

    def run(xs, wg, wu, wd):
        h = cast(jax.nn.silu(_mm(cast(f32(wg)), xs)) * _mm(cast(f32(wu)), xs))
        return _mm(cast(f32(wd)), h)

    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(gate_w.shape[0]):
        g = jnp.where(ids == first + i, w, 0.0).sum(1)
        out += run(xe, gate_w[i], up_w[i], down_w[i]) * g
    out = out.at[:, shared_tokens].add(
        run(xe[:, shared_tokens], shared_gate, shared_up, shared_down))
    return out, ids


def columns(ids, *, first: int, held: int, n_shared: int,
            shared_tokens: slice, tile: int, width: int):
    """Token and expert of each of ``width`` columns, as the coded layer
    lays them out: the tokens that chose each held expert (``ids`` (T, k)),
    in token order, expert by expert, then ``shared_tokens`` once for each
    shared expert (experts numbered 0 .. held + n_shared - 1); each group
    starts on a whole ``tile``.  Padding columns hold token T and expert
    -1.  Returns (tok, expert, columns the groups need)."""
    ids = np.asarray(ids)
    tokens = ids.shape[0]
    groups = [np.nonzero((ids == first + e).any(1))[0] for e in range(held)]
    groups += [np.arange(tokens)[shared_tokens]] * n_shared
    tok = np.full(width, tokens, np.int32)
    expert = np.full(width, -1, np.int32)
    at = 0
    for e, g in enumerate(groups):
        n = min(len(g), max(width - at, 0))
        tok[at:at + n], expert[at:at + n] = g[:n], e
        at += -(-len(g) // tile) * tile
    return tok, expert, at


def expert_columns(a, x, tok, group):
    """Column j of the result is a[group[j]] @ x[:, tok[j]]; a token past
    the end of x reads zeros.  ``a`` is (E, rows, d)."""
    xs = jnp.take(x.astype(jnp.float32), tok, axis=1, mode="fill",
                  fill_value=0)
    y = jnp.einsum("erd,dn->ern", a.astype(jnp.float32), xs,
                   precision=HIGHEST)
    pick = group[None, :] == jnp.arange(a.shape[0])[:, None]      # (E, n)
    return jnp.where(pick[:, None, :], y, 0.0).sum(0)


def coded(G, y, R: int):
    """(C, R) generator times y in R row blocks -> (C * rows/R, n)."""
    c = _mm(jnp.asarray(G, jnp.float32), y.reshape(R, -1))
    return c.reshape(-1, y.shape[1])


def fp8(v):
    """float8 (e4m3) with one scale for the whole tensor."""
    f8 = jnp.float8_e4m3fn
    v = v.astype(jnp.float32)
    scale = jnp.abs(v).max() / float(jnp.finfo(f8).max)
    return (v / scale).astype(f8).astype(jnp.float32) * scale


def flipped(ids, ref_ids):
    """Tokens whose set of chosen experts differs from the reference's."""
    return (jnp.sort(ids, axis=1) != jnp.sort(ref_ids, axis=1)).any(1)


def gap(got, ref, rows=None, cols=None) -> float:
    """Largest entry of |got - ref| over the largest entry of |ref|, on
    the rows and columns the boolean masks keep (all where None)."""
    got = jax.device_put(got, ref.devices().pop()).astype(jnp.float32)
    keep = jnp.ones(ref.shape, bool)
    if rows is not None:
        keep &= jnp.asarray(rows)[:, None]
    if cols is not None:
        keep &= jnp.asarray(cols)[None, :]
    err = jnp.where(keep, jnp.abs(got - ref), 0.0).max()
    return float(err / jnp.where(keep, jnp.abs(ref), 0.0).max())
