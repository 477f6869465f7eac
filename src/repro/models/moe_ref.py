"""Plain reference of a DeepSeek-V3 mixture-of-experts layer (Moonlight-16B-A3B).

The layer's forward pass for a stated share of its experts, in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``,
with no coding, kernels, dispatch or padding: every held expert runs on
every token and is weighted by the token's gate value, which is zero for
the experts the token did not choose.  The equations are DeepSeek-V3's
(arXiv:2412.19437 §2.1.2, eq. 12-16) as its published gate computes them
(HF ``DeepseekV3MoEGate``, ``topk_method`` noaux_tc, ``n_group`` =
``topk_group`` = 1):

  s_{i,t} = sigmoid(u_t . e_i)                       (float32)
  chosen  = TopK_i(s_{i,t} + b_i, K)                 (b: selection only)
  g_{i,t} = s_{i,t} / (sum_{j in chosen} s_{j,t} + 1e-20) * scale, i chosen
  h_t     = sum_{shared} FFN_s(u_t) + sum_i g_{i,t} FFN_i(u_t)
  FFN(u)  = W_down (silu(W_gate u) * (W_up u))

Departures: the residual u_t is left out (the layer's output is what the
block adds to it); a share holds the routed experts [first, first + held)
and runs the shared experts only for ``shared_tokens``, so the outputs of
all shares add up to the whole layer's (the shared experts once per
token); x is (d, T), one column a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gate(x, router, bias, top_k: int, scale: float):
    """(weights (T, k), ids (T, k)) of the chosen experts."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x.astype(jnp.float32).T @ router.astype(
            jnp.float32).T)
    _, ids = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, ids, axis=1)
    return w / (w.sum(1, keepdims=True) + 1e-20) * scale, ids


def ffn(x, w_gate, w_up, w_down):
    """SwiGLU of the columns of x (d, n) -> (d, n)."""
    with jax.default_matmul_precision("highest"):
        return w_down @ (jax.nn.silu(w_gate @ x) * (w_up @ x))


def layer(x, router, bias, gate_w, up_w, down_w, shared_gate, shared_up,
          shared_down, *, first: int, top_k: int, scale: float,
          shared_tokens: slice):
    """The share's output (d, T) in float32 and the chosen ids (T, k).

    ``gate_w``/``up_w`` (held, f, d) and ``down_w`` (held, d, f) are the
    routed experts [first, first + held); ``shared_*`` the shared experts
    as one SwiGLU, (n_shared * f, d) and (d, n_shared * f)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(x)
    w, ids = gate(x, router, f32(bias), top_k, scale)
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(gate_w.shape[0]):
        g = jnp.where(ids == first + i, w, 0.0).sum(1)        # (T,)
        out += ffn(x, f32(gate_w[i]), f32(up_w[i]), f32(down_w[i])) * g
    xs = x[:, shared_tokens]
    out = out.at[:, shared_tokens].add(
        ffn(xs, f32(shared_gate), f32(shared_up), f32(shared_down)))
    return out, ids
