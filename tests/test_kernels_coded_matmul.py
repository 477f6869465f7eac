"""Shape/dtype sweeps: Pallas coded_matmul + lt_encode vs. pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import fountain
from repro.kernels.coded_matmul import (
    coded_matmul, coded_matmul_code, coded_matmul_pallas, coded_matmul_ref)
from repro.kernels.coded_matmul.kernel import (
    VMEM_BUDGET, VMEM_LIMIT, compact, tile_n, vmem_bytes)
from repro.kernels.coded_matmul.ref import lt_encode_ref
from repro.kernels.lt_encode import lt_encode

TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _mk(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "R,K,bm,kdim,ndim,bn",
    [
        (4, 2, 8, 16, 16, None),
        (6, 3, 16, 64, 32, 16),          # cap under every legal tile
        (8, 4, 8, 128, 256, 128),        # MXU-aligned, two n tiles
        (3, 2, 32, 48, 24, None),        # non-square, k not 128-aligned
        (10, 5, 8, 32, 384, 128),        # three n tiles
    ],
)
def test_coded_matmul_sweep(R, K, bm, kdim, ndim, bn, dtype):
    code = fountain.make_lt_code(R=R, K=K, seed=R * 31 + K)
    a = _mk(jax.random.PRNGKey(0), (R * bm, kdim), dtype)
    x = _mk(jax.random.PRNGKey(1), (kdim, ndim), dtype)
    idx, mask = jnp.asarray(code.idx), jnp.asarray(code.mask)
    ref = coded_matmul_ref(a, x, idx, mask, bm)
    out = coded_matmul(
        a, x, idx, mask, bm=bm, bn=bn, use_pallas=True, interpret=True
    )
    assert out.shape == ((R + K) * bm, ndim)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8,
    )


# Neighbour tables (R=6 source blocks) the plans do not make: the kernel
# must give the oracle's answer whatever the layout of the real slots.
TABLES = {
    # systematic degree-1 rows next to dense ±1 parities
    "mixed_degrees": (
        [[0, 0, 0, 0], [1, 0, 0, 0], [2, 0, 0, 0], [3, 0, 0, 0],
         [4, 0, 0, 0], [5, 0, 0, 0], [0, 2, 4, 5], [1, 3, 5, 0]],
        [[1, 0, 0, 0], [1, 0, 0, 0], [1, 0, 0, 0], [1, 0, 0, 0],
         [1, 0, 0, 0], [1, 0, 0, 0], [1, -1, 1, -1], [-1, 1, 1, 0]],
    ),
    # real slots that are not a prefix of their row
    "holes": (
        [[3, 1, 4, 2], [5, 0, 2, 1], [0, 4, 1, 3]],
        [[0, 1, 0, -1], [0, 0, 0, 1], [1, 0, 1, 0]],
    ),
    # a block with no real slot reads zeros
    "empty_block": (
        [[1, 2, 3], [4, 4, 4], [0, 5, 2]],
        [[1, 1, 0], [0, 0, 0], [0, -1, 1]],
    ),
    "d_max_1": ([[5], [0], [3], [2]], [[1], [-1], [1], [1]]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_coded_matmul_tables(table, dtype):
    idx, w = (jnp.asarray(t) for t in TABLES[table])
    bm, kdim, ndim = 16, 48, 256
    a = _mk(jax.random.PRNGKey(10), (6 * bm, kdim), dtype)
    x = _mk(jax.random.PRNGKey(11), (kdim, ndim), dtype)
    ref = coded_matmul_ref(a, x, idx, w.astype(jnp.float32), bm)
    out = coded_matmul(a, x, idx, w, bm=bm, bn=128, use_pallas=True,
                       interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8,
    )
    empty = np.flatnonzero(~np.asarray(w != 0).any(axis=1))
    for b in empty:
        assert not np.asarray(out[b * bm:(b + 1) * bm], np.float32).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "R,K,bm,kdim,ndim,bn",
    [
        (4, 2, 8, 16, 16, None),
        (6, 3, 16, 64, 32, 16),
        (8, 4, 8, 128, 256, 128),
        (10, 5, 8, 32, 384, 128),
    ],
)
def test_one_group_is_the_plain_kernel_bit_for_bit(R, K, bm, kdim, ndim, bn,
                                                    dtype):
    """E = 1 with every tile live in group 0: the plain call's grid, tile
    and result."""
    code = fountain.make_lt_code(R=R, K=K, seed=R * 31 + K)
    a = _mk(jax.random.PRNGKey(0), (R * bm, kdim), dtype)
    x = _mk(jax.random.PRNGKey(1), (kdim, ndim), dtype)
    idx, mask = jnp.asarray(code.idx), jnp.asarray(code.mask)
    size = jnp.dtype(dtype).itemsize
    tile = tile_n(bm, kdim, ndim, size, size, cap=bn)
    plain = coded_matmul_pallas(a, x, idx, mask, bm=bm, bn=bn, interpret=True)
    tiles = ndim // tile
    grouped = coded_matmul_pallas(
        a[None], x, idx, mask, jnp.zeros((tiles,), jnp.int32),
        jnp.full((1,), tiles, jnp.int32), bm=bm, bn=tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(grouped, np.float32),
                                  np.asarray(plain, np.float32))


# Column tiles of grouped products: each tile's matrix, and the live count
# (the tiles past it are dead).
GROUPS = {
    "mixed_sizes": ([0, 0, 2, 1, 1, 1], 6),
    "dead_tail": ([1, 2, 2, 0, 2, 2, 2, 2], 4),
    "one_group": ([1, 1, 1], 3),
    "all_dead": ([0, 0], 0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pallas", "fallback"])
@pytest.mark.parametrize("layout", sorted(GROUPS))
def test_grouped_columns(layout, use_pallas, dtype):
    groups, live = GROUPS[layout]
    E, R, K, bm, kdim, bn = 3, 6, 3, 16, 48, 128
    code = fountain.make_lt_code(R=R, K=K, seed=5)
    idx, w = jnp.asarray(code.idx), jnp.asarray(code.weights)
    a = _mk(jax.random.PRNGKey(12), (E, R * bm, kdim), dtype)
    x = _mk(jax.random.PRNGKey(13), (kdim, len(groups) * bn), dtype)
    out = coded_matmul(a, x, idx, w, bm=bm, bn=bn,
                       tile_group=jnp.asarray(groups, jnp.int32),
                       live_tiles=jnp.asarray([live], jnp.int32),
                       use_pallas=use_pallas, interpret=True)
    assert out.shape == ((R + K) * bm, len(groups) * bn)
    for t, g in enumerate(groups):
        cols = slice(t * bn, (t + 1) * bn)
        got = np.asarray(out[:, cols], np.float32)
        if t >= live:
            assert not got.any()
            continue
        ref = coded_matmul_ref(a[g], x[:, cols], idx, w.astype(jnp.float32),
                               bm)
        np.testing.assert_allclose(got, np.asarray(ref, np.float32),
                                   rtol=TOL[dtype], atol=TOL[dtype] * 8)


def test_compact_puts_real_slots_first_and_repeats_the_last():
    """Padded slots repeat the last real neighbour, so the A index_map
    repeats and no copy is issued for them."""
    idx, w = (jnp.asarray(t) for t in TABLES["holes"])
    deg, idx_c, w_c = compact(idx, w)
    np.testing.assert_array_equal(np.asarray(deg), [2, 1, 2])
    np.testing.assert_array_equal(np.asarray(idx_c),
                                  [[1, 2, 2, 2], [1, 1, 1, 1], [0, 1, 1, 1]])
    np.testing.assert_array_equal(np.asarray(w_c),
                                  [[1, -1, 0, 0], [1, 0, 0, 0], [1, 1, 0, 0]])


def test_tile_n_follows_the_vmem_budget():
    # the coded FFN's bf16 x stays whole: one tile, x read once a call
    assert tile_n(256, 3072, 2048, 2, 2) == 2048
    # f32 at k = n = 4096 gets a narrower tile from the same rule
    assert tile_n(256, 4096, 4096, 4, 4) == 512
    for bn, (bm, k, n, s) in ((2048, (256, 3072, 2048, 2)),
                              (512, (256, 4096, 4096, 4))):
        assert vmem_bytes(bm, k, bn, s, s) <= VMEM_BUDGET < VMEM_LIMIT
    assert tile_n(8, 32, 384, 4, 4, cap=256) == 128
    assert tile_n(8, 32, 24, 4, 4, cap=8) == 24     # n itself is the only tile
    with pytest.raises(ValueError, match="VMEM"):
        tile_n(256, 1 << 17, 256, 2, 2)


def test_coded_matmul_padding_path():
    """An n over 128 that is no multiple of it is padded to lane width;
    k needs no padding (whole-K row blocks)."""
    code = fountain.make_lt_code(R=4, K=2, seed=7)
    a = _mk(jax.random.PRNGKey(2), (4 * 8, 20), jnp.float32)
    x = _mk(jax.random.PRNGKey(3), (20, 200), jnp.float32)
    idx, mask = jnp.asarray(code.idx), jnp.asarray(code.mask)
    ref = coded_matmul_ref(a, x, idx, mask, 8)
    out = coded_matmul(
        a, x, idx, mask, bm=8, bn=128, use_pallas=True, interpret=True
    )
    assert out.shape == (6 * 8, 200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_coded_matmul_code_convenience():
    code = fountain.make_lt_code(R=5, K=2, seed=3)
    a = _mk(jax.random.PRNGKey(4), (5 * 16, 32), jnp.float32)
    x = _mk(jax.random.PRNGKey(5), (32, 16), jnp.float32)
    out = coded_matmul_code(a, x, code, use_pallas=True, interpret=True, bn=16)
    ref = coded_matmul_ref(a, x, jnp.asarray(code.idx),
                           jnp.asarray(code.weights), 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_coded_matmul_systematic_prefix_is_plain_matmul():
    """The systematic prefix of the output must equal A @ x exactly."""
    code = fountain.make_lt_code(R=4, K=3, seed=11)
    a = _mk(jax.random.PRNGKey(6), (4 * 8, 32), jnp.float32)
    x = _mk(jax.random.PRNGKey(7), (32, 16), jnp.float32)
    out = coded_matmul(
        a, x, jnp.asarray(code.idx), jnp.asarray(code.mask),
        bm=8, use_pallas=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out[: 4 * 8]), np.asarray(a @ x), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "R,K,bm,ncols,bc",
    [(4, 2, 8, 16, 8), (8, 4, 16, 128, 128), (5, 3, 8, 24, 8), (2, 1, 128, 256, 256)],
)
def test_lt_encode_sweep(R, K, bm, ncols, bc, dtype):
    code = fountain.make_lt_code(R=R, K=K, seed=R * 17 + K)
    a = _mk(jax.random.PRNGKey(8), (R * bm, ncols), dtype)
    idx, mask = jnp.asarray(code.idx), jnp.asarray(code.mask)
    ref = lt_encode_ref(a, idx, mask, bm)
    out = lt_encode(a, idx, mask, bm=bm, bc=bc, use_pallas=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 4,
    )


@settings(max_examples=10, deadline=None)
@given(
    R=st.integers(2, 8),
    K=st.integers(1, 4),
    bm=st.sampled_from([8, 16]),
    kt=st.integers(1, 3),
    nt=st.integers(1, 3),
    seed=st.integers(0, 100),
)
def test_property_kernel_matches_oracle(R, K, bm, kt, nt, seed):
    """Encode-matmul fusion == encode_ref ∘ matmul for random codes/shapes."""
    code = fountain.make_lt_code(R=R, K=K, seed=seed)
    kdim, ndim = 8 * kt, 8 * nt
    a = _mk(jax.random.PRNGKey(seed), (R * bm, kdim), jnp.float32)
    x = _mk(jax.random.PRNGKey(seed + 1), (kdim, ndim), jnp.float32)
    idx, w = jnp.asarray(code.idx), jnp.asarray(code.weights)
    out = coded_matmul(
        a, x, idx, w, bm=bm, bn=8, use_pallas=True, interpret=True
    )
    enc = fountain.encode(a.reshape(R, bm, kdim), code).reshape(-1, kdim)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(enc @ x), rtol=2e-4, atol=2e-4
    )
