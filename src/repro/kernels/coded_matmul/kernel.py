"""Pallas TPU kernel: fused LT-encode + block matmul (the paper's hot spot).

The paper's helpers compute ``p_{n,i} @ x`` where ``p`` is a fountain-coded
packet.  On TPU, the coded unit is an MXU-aligned row-block and the encode
(a sparse ±1 combination of source blocks) is fused into the matmul:

  for each output column tile n, coded block b, real neighbour slot j:
      acc_a  = sum_j w[b,j] * A[g[n], idx[b,j], :]  (VPU adds, f32 in VMEM)
      out    = round(acc_a) @ X[:, n-tile]          (MXU, once per block)

Grid ``(n_tiles, C, d_max)``.  An A block is a whole-K row block
``(bm, k)``, so there is no reduction axis: one step per (coded block,
neighbour slot) and one DMA per real pair.  The gather uses scalar
prefetch: the neighbour table drives the A BlockSpec's index_map, and the
*encoded* matrix never lands in HBM.

Padded slots.  ``coded_matmul_pallas`` compacts each block's real
neighbours (``mask != 0``) to the front of its row, in their order, and
fills the rest with the block's last real neighbour.  The index_map then
repeats on a padded step, so Pallas issues no copy, and the kernel skips
it (``j >= deg[b]``).  The dot runs at ``j == deg[b] - 1``; a block with no
real slot writes zeros.

Groups.  A is ``(E, R * bm, k)``: E matrices under one code, such as the
experts of a mixture-of-experts layer.  The scalar-prefetched
``tile_group[n]`` names the matrix that column tile n multiplies, so the
columns of x come in groups, each padded to whole tiles.  Tiles from
``live_tiles`` on are dead: they write zeros, and their index_maps repeat
the last live step's blocks, so they issue no copy.  A plain product is
E = 1, every tile in group 0 and live: the same grid, tile and result,
and the same per-step work, since without groups the kernel and its
index_maps leave the group and live-tile lookups out.

x is the same block for every step of an output tile, so it is read from
HBM once per tile; ``tile_n`` makes the tile as wide as the VMEM budget
allows (the whole of n at the coded FFN's widths), so x is read once a
call.

VMEM budget.  ``vmem_bytes`` counts the double-buffered A block, x tile
and output tile, the f32 accumulator, its rounded copy and the dot's f32
result.  ``tile_n`` picks the widest output tile under ``VMEM_BUDGET``
that is a multiple of 128 dividing n (or n itself); ``VMEM_LIMIT`` lets
Mosaic use more than its default scoped VMEM, well under a v5e core's
128 MiB.  bf16 at k=3072, n=2048, bm=256 plans 35.5 MiB with one tile;
f32 at k=n=4096 gets 512-wide tiles.

Rounding.  The encoded block is accumulated in f32 and rounded once to
the dtype of x before the dot, which accumulates in f32.  For bf16 inputs
that is the rounding Mosaic's DEFAULT contraction of f32 operands applies
anyway; f32 inputs keep an f32 block and ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
VMEM_BUDGET = 48 << 20   # the working set a tile choice may plan for
VMEM_LIMIT = 96 << 20    # Mosaic's scoped VMEM limit for the call


def vmem_bytes(bm: int, k: int, bn: int, in_size: int, out_size: int) -> int:
    """VMEM of one step at tile width ``bn`` (item sizes in bytes)."""
    return (2 * bm * k * in_size        # A row block, double-buffered
            + 2 * k * bn * in_size      # x tile, double-buffered
            + 2 * bm * bn * out_size    # output tile, double-buffered
            + bm * k * (4 + in_size)    # f32 accumulator and its rounding
            + bm * bn * 4)              # the dot's f32 result


def tile_n(bm: int, k: int, n: int, in_size: int, out_size: int,
           cap: Optional[int] = None) -> int:
    """Widest output tile under ``VMEM_BUDGET``: n itself or a multiple of
    128 dividing n, at most ``cap`` (the narrowest such tile when none is
    under it)."""
    widths = sorted({n} | {w for w in range(LANE, n, LANE) if n % w == 0})
    if cap is not None:
        widths = [w for w in widths if w <= cap] or widths[:1]
    fits = [w for w in widths
            if vmem_bytes(bm, k, w, in_size, out_size) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"a whole-K row block (bm={bm}, k={k}) with an output tile of "
            f"{widths[0]} needs {vmem_bytes(bm, k, widths[0], in_size, out_size)}"
            f" B of VMEM, over the {VMEM_BUDGET} B budget")
    return fits[-1]


def _kernel(deg_ref, idx_ref, w_ref, grp_ref, live_ref, a_ref, x_ref, o_ref,
            acc_a, *, precision, grouped):
    n, b, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    deg = deg_ref[b]
    real, last, empty = j < deg, j == deg - 1, deg == 0
    if grouped:
        live = n < live_ref[0]
        real, last = live & real, live & last
        empty = empty | jnp.logical_not(live)

    @pl.when(real)
    def _encode():
        part = a_ref[...].astype(jnp.float32) * w_ref[b, j]

        @pl.when(j == 0)
        def _first():
            acc_a[...] = part

        @pl.when(j > 0)
        def _add():
            acc_a[...] += part

    @pl.when(last)
    def _matmul():
        o_ref[...] = jax.lax.dot_general(
            acc_a[...].astype(x_ref.dtype),
            x_ref[...],
            (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    @pl.when(empty & (j == 0))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


def compact(idx: jnp.ndarray, mask: jnp.ndarray):
    """Real slots first: (deg (C,), idx (C, d_max), w (C, d_max)).

    A stable argsort of ``mask == 0`` keeps the real neighbours in order;
    padded slots repeat the block's last real neighbour with weight 0."""
    real = mask != 0
    deg = real.sum(axis=1).astype(jnp.int32)
    order = jnp.argsort(~real, axis=1, stable=True)
    idx_c = jnp.take_along_axis(idx.astype(jnp.int32), order, axis=1)
    w_c = jnp.take_along_axis(mask.astype(jnp.float32), order, axis=1)
    last = jnp.take_along_axis(idx_c, jnp.maximum(deg - 1, 0)[:, None], axis=1)
    slot = jnp.arange(idx.shape[1])[None, :]
    idx_c = jnp.where(slot < deg[:, None], idx_c, last)
    return deg, idx_c, w_c


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "interpret", "out_dtype"),
)
def coded_matmul_pallas(
    a: jnp.ndarray,     # (R * bm, k_dim), or (E, R * bm, k_dim) with groups
    x: jnp.ndarray,     # (k_dim, n_dim)
    idx: jnp.ndarray,   # (C, d_max) int32
    mask: jnp.ndarray,  # (C, d_max) any dtype; nonzero = valid, the weight
    tile_group: Optional[jnp.ndarray] = None,  # (n_dim // bn,) int32
    live_tiles: Optional[jnp.ndarray] = None,  # (1,) int32
    *,
    bm: int,
    bn: Optional[int] = None,
    interpret: bool = False,
    out_dtype=None,
) -> jnp.ndarray:
    """Coded blocks' products, ``(C * bm, n_dim)``.  With ``tile_group``,
    ``bn`` is the column tile of the groups and is used as it is."""
    k_dim, n_dim = x.shape
    C, d_max = idx.shape
    if a.ndim == 2:
        a = a[None]
    if a.shape[2] != k_dim:
        raise ValueError(f"a cols {a.shape[2]} != x rows {k_dim}")
    if a.shape[1] % bm:
        raise ValueError(f"a rows {a.shape[1]} not divisible by bm={bm}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    in_size = max(a.dtype.itemsize, x.dtype.itemsize)
    tile = tile_n(bm, k_dim, n_dim, in_size, out_dtype.itemsize, cap=bn)
    n_tiles = n_dim // tile
    grouped = tile_group is not None
    if not grouped:
        tile_group = jnp.zeros((n_tiles,), jnp.int32)
        live_tiles = jnp.full((1,), n_tiles, jnp.int32)
    elif tile != bn or tile_group.shape != (n_tiles,):
        raise ValueError(f"grouped columns need n ({n_dim}) in whole tiles of "
                         f"bn={bn} (tile {tile}) and one group per tile "
                         f"({tile_group.shape})")

    # Mosaic's default contraction rounds f32 operands to bf16 (measured on
    # a TPU v5e: 2e-3 of the scale at k=4096), so f32 inputs ask for an
    # f32 product.  bf16 inputs keep the one-pass default, whose error is
    # under that of the bf16 output.
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    deg, idx_c, w_c = compact(idx, mask)

    def last_live(n, b, j, live):
        """A dead tile's steps take the last live step's place."""
        if not grouped:     # every tile is live, in group 0
            return n, b, j
        dead = n >= live[0]
        return (jnp.where(dead, jnp.maximum(live[0] - 1, 0), n),
                jnp.where(dead, C - 1, b), jnp.where(dead, d_max - 1, j))

    def a_map(n, b, j, deg, idx, w, grp, live):
        n, b, j = last_live(n, b, j, live)
        return grp[n] if grouped else 0, idx[b, j], 0

    def x_map(n, b, j, deg, idx, w, grp, live):
        return 0, last_live(n, b, j, live)[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_tiles, C, d_max),
        in_specs=[
            # A: whole-K row block idx[b, j] of the tile's matrix
            pl.BlockSpec((pl.squeezed, bm, k_dim), a_map),
            # X: the output tile's columns, all of K
            pl.BlockSpec((k_dim, tile), x_map),
        ],
        out_specs=pl.BlockSpec(
            (bm, tile), lambda n, b, j, deg, idx, w, grp, live: (b, n)),
        scratch_shapes=[pltpu.VMEM((bm, k_dim), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, precision=precision, grouped=grouped),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C * bm, n_dim), out_dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="coded_matmul",
    )
    return fn(deg, idx_c, w_c, tile_group.astype(jnp.int32),
              live_tiles.astype(jnp.int32), a, x)
