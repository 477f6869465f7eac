"""The block-combine kernel (``kernels/block_combine``, interpret mode)
against float64 numpy: sparse and dense rows, with NaN in the blocks no
term names and in the padding's block."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_combine import block_combine, tiles


def _csr(m, named, pad):
    R, W = m.shape
    nnz = (m != 0).sum(1).astype(np.int32)
    blk = np.full((R, W), pad, np.int32)
    coef = np.zeros((R, W), np.float32)
    for r in range(R):
        j = np.flatnonzero(m[r])
        blk[r, :len(j)] = np.asarray(named)[j]
        coef[r, :len(j)] = m[r, j]
    return nnz, blk, coef


@pytest.mark.parametrize("density", [0.2, 1.0], ids=["sparse", "dense"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_combine_matches_float64(dtype, density):
    rng = np.random.default_rng(7)
    nb, bm, n, R = 10, 32, 256, 6
    lost = [3, 7]
    x = jnp.asarray(rng.standard_normal((nb, bm, n)), dtype)
    x = x.at[np.asarray(lost)].set(jnp.nan)
    named = [b for b in range(nb) if b not in lost]
    m = rng.integers(-3, 4, size=(R, len(named))).astype(np.float64)
    m *= rng.random(m.shape) < density
    m[0] = 0.0                                  # a row with no terms
    m[1, 0] = 0.625                             # not an integer
    nnz, blk, coef = _csr(m, named, pad=lost[0])
    tb, tn = tiles(bm, n, dtype)
    out = block_combine(jnp.asarray(nnz), jnp.asarray(blk),
                        jnp.asarray(coef), x, tb=tb, tn=tn, interpret=True)
    assert out.shape == (R, bm, n) and out.dtype == x.dtype
    ref = np.einsum("rj,jbn->rbn", m,
                    np.asarray(x[np.asarray(named)], np.float64))
    got = np.asarray(out, np.float64)
    assert np.isfinite(got).all()
    # float32 sums, rounded once to the output dtype
    tol = (2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5) * np.abs(ref).max()
    np.testing.assert_allclose(got, ref, atol=tol, rtol=0)
    assert not got[0].any()


@pytest.mark.parametrize("bm,n,dtype,want", [
    (256, 2048, jnp.bfloat16, (16, 1024)),
    (128, 39936, jnp.bfloat16, (16, 1024)),
    (32, 256, jnp.float32, (32, 256)),
    (8, 384, jnp.float32, (8, 128)),
    (8, 16, jnp.float32, None),                # narrower than a lane tile
    (8, 256, jnp.bfloat16, None),              # fewer rows than a bf16 tile
])
def test_tiles(bm, n, dtype, want):
    assert tiles(bm, n, dtype) == want
