"""Plain reference of a row-coded matrix product.

The product y = A x in float32 at full precision (``Precision.HIGHEST``:
a TPU's default float32 matmul rounds its inputs to bf16), and the coded
blocks' products G (A x): coded block c is the sum over source blocks r of
G[c, r] times y's r-th block of rows.  ``G`` is the code's generator in the
order the coded blocks are laid out, the definition of what each coded
block holds.  It imports nothing of the program under test.

``fp8`` rounds a tensor to float8 (e4m3) with one scale for the whole
tensor: the control computes the product from fp8 inputs and rounds the
coded products to fp8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def product(a, x):
    return jnp.matmul(a.astype(jnp.float32), x.astype(jnp.float32),
                      precision=HIGHEST)


def coded(G, y, R: int):
    """(C, R) generator times y in R row blocks -> (C * rows/R, n)."""
    c = jnp.matmul(jnp.asarray(G, jnp.float32), y.reshape(R, -1),
                   precision=HIGHEST)
    return c.reshape(-1, y.shape[1])


def fp8(v):
    f8 = jnp.float8_e4m3fn
    v = v.astype(jnp.float32)
    scale = jnp.abs(v).max() / float(jnp.finfo(f8).max)
    return (v / scale).astype(f8).astype(jnp.float32) * scale


def gap(got, ref) -> float:
    """Largest entry of |got - ref| over the largest entry of |ref|."""
    got = jax.device_put(got, ref.devices().pop()).astype(jnp.float32)
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
