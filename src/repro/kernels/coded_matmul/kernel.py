"""Pallas TPU kernel: fused LT-encode + block matmul (the paper's hot spot).

The paper's helpers compute ``p_{n,i} @ x`` where ``p`` is a fountain-coded
packet.  On TPU, the coded unit is an MXU-aligned row-block and the encode
(a sparse ±1 combination of source blocks) is fused into the matmul:

  for each coded block b, output tile n, reduction tile k:
      acc_a  = sum_j mask[b,j] * A[idx[b,j], k-tile]     (VPU adds, VMEM)
      acc_o += acc_a @ X[k-tile, n-tile]                 (MXU)

The gather over ``idx`` uses scalar prefetch: the neighbour table drives the
``A`` BlockSpec index_map, so each A tile is DMA'd HBM->VMEM exactly once
per (b, k, j) and the *encoded* matrix never materializes in HBM.  Vs.
encode-then-matmul this saves a full HBM round trip of the coded A
(write C*bm*K + read C*bm*K bytes).

Grid: (C, n_tiles, k_tiles, d_max) — j innermost so the fp32 VMEM
accumulators live across the encode reduction; k next so output tiles
accumulate across the matmul reduction.

VMEM working set per step: A tile (bm, bk) + X tile (bk, bn) + acc_a
(bm, bk) f32 + acc_o (bm, bn) f32 + out tile — with the default
bm=bk=bn=256 and bf16 inputs that is 256*256*(2+2+4+4+2) B ~ 0.9 MB, well
inside the ~16 MB v5e VMEM budget; tiles are 128-aligned for the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, mask_ref, a_ref, x_ref, o_ref, acc_a, acc_o, *, d_max, nk,
            precision):
    j = pl.program_id(3)
    k = pl.program_id(2)

    @pl.when(j == 0)
    def _init_acc_a():
        acc_a[...] = jnp.zeros_like(acc_a)

    @pl.when((j == 0) & (k == 0))
    def _init_acc_o():
        acc_o[...] = jnp.zeros_like(acc_o)

    b = pl.program_id(0)
    m = mask_ref[b, j].astype(jnp.float32)
    acc_a[...] += a_ref[...].astype(jnp.float32) * m

    @pl.when(j == d_max - 1)
    def _matmul():
        acc_o[...] += jax.lax.dot_general(
            acc_a[...],
            x_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32,
        )

        @pl.when(k == nk - 1)
        def _write():
            o_ref[...] = acc_o[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "interpret", "out_dtype"),
)
def coded_matmul_pallas(
    a: jnp.ndarray,     # (R * bm, k_dim)
    x: jnp.ndarray,     # (k_dim, n_dim)
    idx: jnp.ndarray,   # (C, d_max) int32
    mask: jnp.ndarray,  # (C, d_max) any dtype; nonzero = valid
    *,
    bm: int,
    bk: int,
    bn: int,
    interpret: bool = False,
    out_dtype=None,
) -> jnp.ndarray:
    k_dim, n_dim = x.shape
    C, d_max = idx.shape
    if a.shape[1] != k_dim:
        raise ValueError(f"a cols {a.shape[1]} != x rows {k_dim}")
    if k_dim % bk or n_dim % bn or a.shape[0] % bm:
        raise ValueError(
            f"shapes (a={a.shape}, x={x.shape}) not divisible by "
            f"blocks (bm={bm}, bk={bk}, bn={bn}); pad in ops.py"
        )
    nk, nn = k_dim // bk, n_dim // bn
    out_dtype = out_dtype or x.dtype

    grid = (C, nn, nk, d_max)
    # Mosaic's default contraction for f32 operands rounds them to bf16
    # (measured on a TPU v5e: 2e-3 of the scale at k=4096); f32 inputs get
    # an f32 product.  bf16 inputs keep the one-pass default, whose error
    # is under that of the bf16 output.
    precision = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kernel = functools.partial(_kernel, d_max=d_max, nk=nk,
                               precision=precision)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec(  # A: gather row-block idx[b, j], k-tile k
                (bm, bk),
                lambda b, n, k, j, idx_ref, mask_ref: (idx_ref[b, j], k),
            ),
            pl.BlockSpec(  # X: (k, n) tile
                (bk, bn),
                lambda b, n, k, j, idx_ref, mask_ref: (k, n),
            ),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda b, n, k, j, idx_ref, mask_ref: (b, n)
        ),
        scratch_shapes=[
            pltpu.VMEM((bm, bk), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C * bm, n_dim), out_dtype),
        interpret=interpret,
        name="coded_matmul",
    )
    return fn(idx.astype(jnp.int32), mask.astype(jnp.float32), a, x)
