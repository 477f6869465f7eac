"""Unit + property tests for the LT/fountain coding layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import fountain


def test_ideal_soliton_is_distribution():
    p = fountain.ideal_soliton(64)
    assert p.shape == (64,)
    assert np.all(p >= 0)
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-12)


def test_robust_soliton_is_distribution():
    for R in (2, 8, 100, 1000):
        p = fountain.robust_soliton(R)
        assert p.shape == (R,)
        assert np.all(p >= -1e-15)
        np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-12)


def test_code_structure_systematic():
    code = fountain.make_lt_code(R=16, K=6, seed=3)
    assert code.n_coded == 22
    degs = code.degrees()
    # systematic prefix has degree exactly 1, identity neighbours
    assert np.all(degs[:16] == 1)
    assert np.array_equal(code.idx[:16, 0], np.arange(16))
    # parities have degree >= 2 (degree-1 parities are resampled)
    assert np.all(degs[16:] >= 2)


def test_coverage_guarantee():
    # every source must appear in at least one parity when K > 0
    for seed in range(10):
        code = fountain.make_lt_code(R=24, K=4, seed=seed)
        par_rows = code.idx[24:][code.mask[24:]]
        assert set(range(24)) <= set(par_rows.tolist())


def test_encode_matches_dense_generator():
    code = fountain.make_lt_code(R=12, K=5, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 7))
    coded = fountain.encode(x, code)
    G = jnp.asarray(code.dense_generator())
    np.testing.assert_allclose(np.asarray(coded), np.asarray(G @ x), rtol=1e-5)


def test_decode_identity_when_nothing_lost():
    code = fountain.make_lt_code(R=10, K=3, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 4))
    coded = fountain.encode(x, code)
    ids = np.arange(13)
    dec, method = fountain.decode(coded, code, ids)
    assert method == "peel"
    np.testing.assert_allclose(np.asarray(dec), np.asarray(x), atol=1e-5)


@pytest.mark.parametrize("n_lost", [1, 2, 3])
def test_decode_recovers_after_losses(n_lost):
    code = fountain.make_lt_code(R=20, K=8, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(2), (20, 3, 2))
    coded = fountain.encode(x, code)
    rng = np.random.default_rng(n_lost)
    lost = rng.choice(20, size=n_lost, replace=False)  # lose systematic blocks
    keep = np.setdiff1d(np.arange(28), lost)
    dec, _ = fountain.decode(coded[keep], code, keep)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(x), atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    R=st.integers(min_value=4, max_value=40),
    K_frac=st.floats(min_value=0.2, max_value=0.6),
    seed=st.integers(min_value=0, max_value=1000),
    data=st.data(),
)
def test_property_decode_inverts_encode(R, K_frac, seed, data):
    """Any loss pattern of <= K/2 blocks must decode exactly (peel or dense)."""
    K = max(2, int(R * K_frac))
    code = fountain.make_lt_code(R=R, K=K, seed=seed)
    n_lost = data.draw(st.integers(min_value=0, max_value=K // 2))
    rng = np.random.default_rng(seed + 1)
    lost = rng.choice(R + K, size=n_lost, replace=False)
    keep = np.setdiff1d(np.arange(R + K), lost)
    x = jax.random.normal(jax.random.PRNGKey(seed), (R, 3))
    coded = fountain.encode(x, code)
    try:
        dec, _ = fountain.decode(coded[keep], code, keep)
    except ValueError:
        # rank-deficient loss pattern: legal for a fountain code — the
        # contract is probabilistic; just skip (rate tracked separately).
        return
    np.testing.assert_allclose(np.asarray(dec), np.asarray(x), atol=1e-3)


def test_peel_plan_none_when_undecodable():
    code = fountain.make_lt_code(R=8, K=0, seed=0)
    # lose a systematic block with no parity: must stall
    keep = np.setdiff1d(np.arange(8), [3])
    assert fountain.peel_decode_plan(code, keep) is None


def test_failure_prob_small_for_modest_loss():
    p = fountain.decode_failure_prob(R=64, K=16, n_lost=4, trials=50, seed=0)
    # peeling may stall on small codes (falls back to dense solve), but true
    # unrecoverability must be rare
    assert p["unrecoverable"] <= 0.05
    assert p["peel_stall"] <= 0.5


def _peel_case():
    code = fountain.make_lt_code(R=20, K=8, seed=5)
    return code, np.setdiff1d(np.arange(28), [3, 9])


def _dense_case():
    # Losing coded blocks 0, 2 and 11 stalls peeling; the rest keep full
    # rank, so the dense solve recovers.
    code = fountain.make_lt_code(R=12, K=6, seed=0, parity_degree=6)
    return code, np.setdiff1d(np.arange(18), [0, 2, 11])


CASES = {"peel": _peel_case, "dense": _dense_case}


@pytest.mark.parametrize("path", ["peel", "dense"])
def test_decode_matrix_left_inverts_the_received_rows(path):
    """The matrix maps the received rows of G back to the identity; the
    peel matrix is the plan's, in small integers."""
    code, keep = CASES[path]()
    m, got = fountain.decode_matrix(code, keep)
    assert got == path
    G = code.dense_generator()[keep].astype(np.float64)
    np.testing.assert_allclose(m @ G, np.eye(code.R), atol=1e-9)
    if path == "peel":
        plan = fountain.peel_decode_plan(code, keep)
        np.testing.assert_array_equal(m, fountain.plan_matrix(plan,
                                                              len(keep)))
        np.testing.assert_array_equal(m, np.round(m))


@pytest.mark.parametrize("path", ["peel", "dense"])
def test_decoder_holds_the_matrix_nonzeros(path):
    """A peel matrix's decoder holds each row's nonzeros as terms naming
    the payload positions of the received blocks (the padding names one
    of them); a pseudo-inverse's holds the whole matrix and those
    positions."""
    code, keep = CASES[path]()
    m, _ = fountain.decode_matrix(code, keep)
    blocks = 2 * np.arange(len(keep)) + 1      # received blocks' positions
    dec = fountain.decoder(m, blocks, path)
    if path == "dense":
        assert isinstance(dec, fountain.DenseDecoder)
        assert dec.m.dtype == np.float32 and dec.rx.dtype == np.int32
        np.testing.assert_array_equal(dec.rx, blocks)
        np.testing.assert_allclose(dec.m, m, rtol=2 ** -23, atol=0)
        return
    assert isinstance(dec, fountain.SparseDecoder)
    assert dec.coef.dtype == np.float32 and dec.blk.shape == m.shape
    back = np.zeros((code.R, 2 * len(keep) + 1))
    for r in range(code.R):
        t = dec.nnz[r]
        back[r, dec.blk[r, :t]] += dec.coef[r, :t]
        assert not dec.coef[r, t:].any()
    np.testing.assert_allclose(back[:, blocks], m, rtol=2 ** -23, atol=0)
    assert not np.delete(back, blocks, axis=1).any()
    assert np.isin(dec.blk, blocks).all()
    assert (dec.nnz == np.count_nonzero(m, axis=1)).all()


@pytest.mark.parametrize("path", ["peel", "dense"])
def test_decode_against_float64_reference(path):
    code, keep = CASES[path]()
    x = np.random.default_rng(0).standard_normal((code.R, 5, 3))
    coded = np.einsum("cr,rbn->cbn", code.dense_generator().astype(np.float64),
                      x)
    dec, got = fountain.decode(jnp.asarray(coded[keep], jnp.float32), code,
                               keep)
    assert got == path
    assert dec.shape == x.shape and dec.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(dec, np.float64), x, atol=1e-4)


def test_decode_compiles_one_program_per_payload_shape():
    code = fountain.make_lt_code(R=20, K=8, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(3), (20, 7, 5))
    coded = fountain.encode(x, code)
    before = fountain.apply_decoder._cache_size()
    for lost in ([0, 1], [2, 20], [5, 27]):
        keep = np.setdiff1d(np.arange(28), lost)
        dec, _ = fountain.decode(coded[keep], code, keep)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(x), atol=1e-4)
    assert fountain.apply_decoder._cache_size() - before == 1
