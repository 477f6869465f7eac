"""Pure-jnp oracle for the fused LT-encode + block-matmul kernel.

Semantics: the source matrix ``a`` is ``R`` row-blocks of ``bm`` rows.  For
each coded block ``b`` (of ``C = R + K`` total),

    A_enc[b] = sum_j mask[b, j] * A[idx[b, j]]          (LT encode)
    V[b]     = A_enc[b] @ x                             (block matmul)

Returns V as a ``(C * bm, n)`` matrix.  This is exactly
``fountain.encode`` followed by a dense matmul; the Pallas kernel fuses the
two so the encoded ``A`` never round-trips through HBM.
"""

from __future__ import annotations

import jax.numpy as jnp


def coded_matmul_ref(
    a: jnp.ndarray,      # (R * bm, k_dim)
    x: jnp.ndarray,      # (k_dim, n_dim)
    idx: jnp.ndarray,    # (C, d_max) int32 — source-block neighbours
    mask: jnp.ndarray,   # (C, d_max) bool/float — neighbour validity
    bm: int,
) -> jnp.ndarray:
    r_blocks = a.shape[0] // bm
    if a.shape[0] != r_blocks * bm:
        raise ValueError(f"a rows {a.shape[0]} not divisible by bm={bm}")
    blocks = a.reshape(r_blocks, bm, a.shape[1])
    gathered = jnp.take(blocks, idx, axis=0)            # (C, d_max, bm, k)
    m = mask.astype(a.dtype)[:, :, None, None]
    enc = (gathered * m).sum(axis=1)                    # (C, bm, k)
    out = jnp.einsum(
        "cbk,kn->cbn", enc.astype(jnp.float32), x.astype(jnp.float32)
    )
    return out.reshape(-1, x.shape[1]).astype(x.dtype)


def coded_matmul_grouped_ref(
    a: jnp.ndarray,           # (E, R * bm, k_dim)
    x: jnp.ndarray,           # (k_dim, n_dim), n_dim = n_tiles * bn
    idx: jnp.ndarray,
    mask: jnp.ndarray,
    bm: int,
    tile_group: jnp.ndarray,  # (n_tiles,) matrix of each column tile
    live_tiles: jnp.ndarray,  # (1,) tiles that hold columns
    bn: int,
) -> jnp.ndarray:
    """Grouped columns: column c is multiplied by the coded blocks of
    ``a[tile_group[c // bn]]``; columns of dead tiles come out zero."""
    col = jnp.arange(x.shape[1]) // bn
    group = jnp.where(col < live_tiles[0], tile_group[col], -1)
    out = jnp.zeros((idx.shape[0] * bm, x.shape[1]), jnp.float32)
    for e in range(a.shape[0]):
        x_e = jnp.where(group == e, x, jnp.zeros_like(x))
        out += coded_matmul_ref(a[e], x_e, idx, mask, bm).astype(jnp.float32)
    return out.astype(x.dtype)


def lt_encode_ref(
    a: jnp.ndarray,      # (R * bm, n_cols)
    idx: jnp.ndarray,    # (C, d_max)
    mask: jnp.ndarray,   # (C, d_max)
    bm: int,
) -> jnp.ndarray:
    """Encode-only oracle: returns (C * bm, n_cols)."""
    r_blocks = a.shape[0] // bm
    blocks = a.reshape(r_blocks, bm, a.shape[1])
    gathered = jnp.take(blocks, idx, axis=0)
    m = mask.astype(a.dtype)[:, :, None, None]
    enc = (gathered * m).sum(axis=1)
    return enc.reshape(-1, a.shape[1])
