"""Public wrapper for the fused coded matmul.

Handles padding n to lane multiples, the tile choice and its counters, the
jnp fallback (used on CPU and in the dry-run lowering), and LTCode
plumbing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.fountain import LTCode
from ...utils import tracing
from .kernel import LANE, coded_matmul_pallas, tile_n
from .ref import coded_matmul_grouped_ref, coded_matmul_ref


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def coded_matmul(
    a: jnp.ndarray,
    x: jnp.ndarray,
    idx: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    bm: int,
    bn: Optional[int] = None,
    tile_group: Optional[jnp.ndarray] = None,
    live_tiles: Optional[jnp.ndarray] = None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """V[b*bm:(b+1)*bm] = (sum_j mask[b,j] A[idx[b,j]]) @ x for coded block b.

    a: (R*bm, k_dim); x: (k_dim, n_dim); idx/mask: (C, d_max).
    Returns (C*bm, n_dim).  ``bn`` bounds the kernel's output tile width,
    which is otherwise set by the shapes (``kernel.tile_n``).

    Grouped columns: ``a`` is (E, R*bm, k_dim), n_dim is whole ``bn``-wide
    tiles, ``tile_group`` (n_dim // bn,) names each tile's matrix and
    ``live_tiles`` (1,) counts the tiles that hold columns; the rest come
    out zero.

    Each trace of the Pallas path counts ``coded_matmul.grid_steps``
    (n_tiles * C * d_max), and ``coded_matmul.real_pairs`` (nonzero mask
    entries) where ``mask`` is a host array, as a plan's tables are.
    """
    real_pairs = (int(np.count_nonzero(mask))
                  if use_pallas and isinstance(mask, np.ndarray) else None)
    if tile_group is not None and (bn is None or x.shape[1] % bn):
        raise ValueError(f"grouped columns need whole tiles: n={x.shape[1]}, "
                         f"bn={bn}")
    return _coded_matmul(a, x, idx, mask, tile_group, live_tiles, bm=bm,
                         bn=bn, use_pallas=use_pallas, interpret=interpret,
                         real_pairs=real_pairs)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "use_pallas", "interpret", "real_pairs"),
)
def _coded_matmul(a, x, idx, mask, tile_group, live_tiles, *, bm, bn,
                  use_pallas, interpret, real_pairs):
    if not use_pallas:
        if tile_group is None:
            return coded_matmul_ref(a, x, idx, mask, bm)
        return coded_matmul_grouped_ref(a, x, idx, mask, bm, tile_group,
                                        live_tiles, bn)
    k_dim, n_dim = x.shape
    if tile_group is None:
        # Lane-aligned n, and the widest tile the VMEM budget allows.
        np_ = n_dim if n_dim <= LANE else _pad_to(n_dim, LANE)
        x = jnp.pad(x, ((0, 0), (0, np_ - n_dim)))
        bn = tile_n(bm, k_dim, np_, max(a.dtype.itemsize, x.dtype.itemsize),
                    x.dtype.itemsize, cap=bn)
    tracing.count("coded_matmul.grid_steps", x.shape[1] // bn * idx.size)
    if real_pairs is not None:
        tracing.count("coded_matmul.real_pairs", real_pairs)
    out = coded_matmul_pallas(a, x, idx, mask, tile_group, live_tiles, bm=bm,
                              bn=bn, interpret=interpret)
    return out[:, :n_dim]


def coded_matmul_code(
    a: jnp.ndarray,
    x: jnp.ndarray,
    code: LTCode,
    *,
    bm: Optional[int] = None,
    **kw,
) -> jnp.ndarray:
    """Convenience: drive the kernel from an LTCode. ``a`` rows must split
    into ``code.R`` equal blocks (bm inferred when not given)."""
    if bm is None:
        if a.shape[0] % code.R:
            raise ValueError(f"a rows {a.shape[0]} not divisible by R={code.R}")
        bm = a.shape[0] // code.R
    return coded_matmul(
        a, x, jnp.asarray(code.idx), jnp.asarray(code.weights), bm=bm, **kw
    )


def flops(R: int, K: int, bm: int, k_dim: int, n_dim: int, d_mean: float) -> dict:
    """Roofline terms for one fused coded matmul (per §Roofline).

    Returns flops of the MXU matmul part, VPU encode adds, and HBM bytes
    moved (bf16), for napkin math in benchmarks/kernel_bench.py.  Fused,
    A is read once per real (block, neighbour) pair, d_mean * C row blocks,
    not once per C * d_max slot (padded slots issue no copy); x once and
    the coded output once, with one output tile (``kernel.tile_n``).
    """
    C = R + K
    matmul = 2.0 * C * bm * k_dim * n_dim
    encode_adds = d_mean * C * bm * k_dim
    bytes_fused = 2.0 * (d_mean * C * bm * k_dim + k_dim * n_dim + C * bm * n_dim)
    bytes_unfused = bytes_fused + 2.0 * 2.0 * C * bm * k_dim  # write+read A_enc
    return dict(
        matmul_flops=matmul,
        encode_flops=encode_adds,
        hbm_bytes_fused=bytes_fused,
        hbm_bytes_unfused=bytes_unfused,
    )
