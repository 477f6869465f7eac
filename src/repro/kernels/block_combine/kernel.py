"""Pallas TPU kernel: output blocks as sparse linear combinations of input
blocks.

    out[r] = sum_{t < nnz[r]} coef[r, t] * x[blk[r, t]]        (r < R)

``x`` is (n_blocks, bm, n) and ``out`` (R, bm, n).  Row r's terms are the
first ``nnz[r]`` of ``blk[r]``/``coef[r]`` (the rest is padding), held in
SMEM by scalar prefetch.  It is the collector's recovery on the peel path
(``core.fountain.apply_decoder`` of a ``SparseDecoder``): the decode
matrix's nonzeros, each with the position of its received block, so a
block that no term names (a lost shard's, whatever it holds) never enters
the arithmetic.

Grid ``(n // tn, bm // tb)``, both axes parallel.  A step holds the
(tb, tn) tile of every input block in VMEM, converted to float32 once, and
forms the R output tiles one after another, each summed in registers over
its own terms.  So each input and output byte moves once, and the VPU work
goes with the matrix's nonzeros: a peel matrix of the +-1 codes is mostly
a single 1 a row.  Products and sums are float32 (a matmul's
``Precision.HIGHEST``).

Why not the MXU: the contraction runs over the blocks, the payload's major
axis, so a matmul has to lay the payload out anew (XLA transposes it to
(bm, n_blocks, n) and the result back), and the MXU streams every payload
row whatever the contraction's length.  On a TPU v5e, at the Moonlight
expert layer's peel matrices (~2 terms a source), the kernel took 1.7 and
1.4 ms where that matmul took 4.6 and 3.6 ms; a dense pseudo-inverse (70
terms a source) is the other way round, so it stays on the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACC_ELEMS = 16 * 1024     # one output tile's f32 accumulator: 16 vregs
VMEM_LIMIT = 64 << 20


def tiles(bm: int, n: int, dtype) -> Optional[Tuple[int, int]]:
    """(tb, tn) for blocks of (bm, n) in ``dtype``: the widest tn of 1024,
    512, 256, 128 that divides n, and the tallest tb that divides bm with
    tb * tn <= ``ACC_ELEMS``, in whole sublane tiles of the dtype.  None
    where no such tile exists."""
    sub = 32 // jnp.dtype(dtype).itemsize     # rows of a packed (8, 128) tile
    for tn in (1024, 512, 256, 128):
        if n % tn:
            continue
        tb = min(bm, ACC_ELEMS // tn) // sub * sub
        while tb >= sub and bm % tb:
            tb -= sub
        if tb >= sub:
            return tb, tn
    return None


def _kernel(nnz_ref, blk_ref, coef_ref, x_ref, o_ref, xf_ref, *, width):
    xf_ref[...] = x_ref[...].astype(jnp.float32)

    def row(r, carry):
        def term(t, acc):
            p = r * width + t
            return acc + coef_ref[p] * xf_ref[blk_ref[p]]

        acc = jax.lax.fori_loop(0, nnz_ref[r], term,
                                jnp.zeros(o_ref.shape[1:], jnp.float32))
        o_ref[r] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], row, 0)


def block_combine(nnz: jnp.ndarray, blk: jnp.ndarray, coef: jnp.ndarray,
                  x: jnp.ndarray, *, tb: int, tn: int,
                  interpret: bool = False) -> jnp.ndarray:
    """``out[r] = sum_{t < nnz[r]} coef[r, t] * x[blk[r, t]]``: ``nnz``
    (R,) int32, ``blk`` (R, W) int32, ``coef`` (R, W) float32, ``x``
    (n_blocks, bm, n) -> (R, bm, n) in x's dtype."""
    R, width = blk.shape
    nb, bm, n = x.shape
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, bm // tb),
        in_specs=[pl.BlockSpec((nb, tb, tn), lambda i, k, *_: (0, k, i))],
        out_specs=pl.BlockSpec((R, tb, tn), lambda i, k, *_: (0, k, i)),
        scratch_shapes=[pltpu.VMEM((nb, tb, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, width=width),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((R, bm, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="block_combine",
    )(nnz, blk.reshape(-1), coef.reshape(-1), x)
