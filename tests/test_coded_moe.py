"""The coded expert layer (``core/coded_moe.py``) against the plain
reference (``models/moe_ref.py``) on seeded random weights at small widths:
DeepSeek-V3's router, every single-shard loss, the Pallas kernel in
interpret mode and the jnp fallback, the shares adding up to the uncut
layer, ragged routing, and one compile per column buffer size."""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coded_moe as CM
from repro.models import moe_ref
from repro.models.moe import route

D, F, E, K, N_SHARED, SHARES, T = 128, 64, 16, 4, 2, 4, 64
SCALE = 2.446
N_SHARDS, BN = 4, 128


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, shape, fan: jax.random.normal(k, shape) / np.sqrt(fan)
    return dict(
        router=n(ks[0], (E, D), D), bias=0.05 * jax.random.normal(ks[1], (E,)),
        gate=n(ks[2], (E, F, D), D), up=n(ks[3], (E, F, D), D),
        down=n(ks[4], (E, D, F), F),
        shared_gate=n(ks[5], (N_SHARED * F, D), D),
        shared_up=n(ks[6], (N_SHARED * F, D), D),
        shared_down=n(ks[7], (D, N_SHARED * F), N_SHARED * F))


def _x(seed=1, tokens=T):
    return jax.random.normal(jax.random.PRNGKey(seed), (D, tokens))


def _share(i):
    return CM.ExpertShare(d_model=D, d_expert=F, n_experts=E, top_k=K,
                          n_shared=N_SHARED, share=i, n_shares=SHARES,
                          scoring="sigmoid", scale=SCALE)


def _layer(share, use_pallas):
    layer = CM.CodedExperts(share, n_shards=N_SHARDS, overhead=0.5,
                            bm_gate_up=16, bm_down=16,
                            use_pallas=use_pallas, interpret=True)
    assert layer.tile(T) == BN
    return layer


def _params(w, share):
    held = slice(share.first, share.first + share.held)
    w_gu, w_down = CM.pack(w["gate"][held], w["up"][held], w["down"][held],
                           w["shared_gate"], w["shared_up"], w["shared_down"])
    return dict(router=w["router"], bias=w["bias"], w_gu=w_gu, w_down=w_down)


def _ref(w, x, share=None):
    """The reference for ``share``, or for the uncut layer."""
    first, held = (0, E) if share is None else (share.first, share.held)
    sl = slice(first, first + held)
    shared = (slice(0, x.shape[1]) if share is None
              else share.shared_slice(x.shape[1]))
    return moe_ref.layer(x, w["router"], w["bias"], w["gate"][sl],
                         w["up"][sl], w["down"][sl], w["shared_gate"],
                         w["shared_up"], w["shared_down"], first=first,
                         top_k=K, scale=SCALE, shared_tokens=shared)


def _gap(got, ref):
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


# -- the router ---------------------------------------------------------------

@pytest.mark.parametrize("with_bias", [False, True])
def test_router_matches_the_reference_gate(with_bias):
    w = _weights()
    x = _x()
    bias = w["bias"] if with_bias else jnp.zeros((E,))
    logits = jnp.einsum("dt,ed->te", x, w["router"],
                        precision=jax.lax.Precision.HIGHEST)
    got_w, got_ids, scores = route(logits, K, scoring="sigmoid", bias=bias,
                                   scale=SCALE)
    ref_w, ref_ids = moe_ref.gate(x, w["router"], bias, K, SCALE)
    np.testing.assert_array_equal(np.sort(got_ids, 1), np.sort(ref_ids, 1))
    np.testing.assert_allclose(got_w, ref_w, rtol=1e-6, atol=1e-6)
    # Normalised over the k chosen, then scaled.
    np.testing.assert_allclose(got_w.sum(1), SCALE, rtol=1e-6)
    np.testing.assert_allclose(scores, jax.nn.sigmoid(logits), rtol=1e-6)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_bias_changes_the_choice_and_not_the_weights(scoring):
    logits = jax.random.normal(jax.random.PRNGKey(3), (32, E))
    bias = jnp.zeros((E,)).at[5].set(10.0)
    w0, ids0, s = route(logits, K, scoring=scoring, scale=SCALE)
    w1, ids1, _ = route(logits, K, scoring=scoring, bias=bias, scale=SCALE)
    # Expert 5 is chosen for every token once the bias favours it ...
    assert (np.asarray(ids1) == 5).any(1).all()
    assert not (np.asarray(ids0) == 5).any(1).all()
    # ... and each chosen expert is weighted by its unbiased score.
    chosen = jnp.take_along_axis(s, ids1, axis=1)
    np.testing.assert_allclose(
        w1, chosen / chosen.sum(1, keepdims=True) * SCALE, rtol=1e-6)


# -- the layer ----------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["fallback", "pallas"])
@pytest.mark.parametrize("lost", range(N_SHARDS))
def test_layer_matches_reference_for_every_lost_shard(lost, use_pallas):
    w, x = _weights(), _x()
    share = _share(1)
    out, ids, coded, cols = _layer(share, use_pallas)(_params(w, share), x,
                                                      lost)
    ref, ref_ids = _ref(w, x, share)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    assert out.shape == (D, T) and out.dtype == x.dtype
    assert _gap(out, ref) < 1e-5
    # The lost shard's coded blocks never arrived.
    rows = _layer(share, False).plan_gu.blocks_per_shard * 16
    assert not np.asarray(coded[lost * rows:(lost + 1) * rows]).any()
    routed, shared, live = np.asarray(cols.stats)
    held = (np.asarray(ids) >= share.first) & (
        np.asarray(ids) < share.first + share.held)
    assert routed == held.sum() and shared == N_SHARED * T // SHARES
    assert live % BN == 0 and live <= cols.tok.shape[0]


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["fallback", "pallas"])
def test_shares_add_up_to_the_uncut_layer(use_pallas):
    """Each share holds E / SHARES experts and runs the shared experts for
    its own slice of the tokens; the shares' outputs add up to the whole
    layer, the shared experts counted once per token."""
    w, x = _weights(), _x()
    total = 0.0
    for i in range(SHARES):
        share = _share(i)
        out, *_ = _layer(share, use_pallas)(_params(w, share), x, i % N_SHARDS)
        total = total + out
    full, _ = _ref(w, x)
    assert _gap(total, full) < 1e-5


def _ragged_bias(case):
    """A bias that forces the routing of ``case`` onto share 0's experts
    0..3."""
    bias = jnp.zeros((E,))
    if case == "expert_without_tokens":
        return bias.at[2].set(-10.0)
    if case == "expert_with_every_token":
        return bias.at[1].set(10.0)
    return bias


@pytest.mark.parametrize("case", ["expert_without_tokens",
                                  "expert_with_every_token",
                                  "padded_tail_tiles"])
def test_ragged_routing(case):
    w = dict(_weights(), bias=_ragged_bias(case))
    x = _x(tokens=96)
    share = _share(0)
    layer = _layer(share, True)
    if case == "padded_tail_tiles":
        # Two dead tiles past the last group, as a ladder size above the
        # tiles a routing needs leaves.
        need = layer.columns
        layer.columns = lambda counts, tokens: need(counts, tokens) + 2 * BN
    out, ids, coded, cols = layer(_params(w, share), x, 2)
    ref, _ = _ref(w, x, share)
    assert _gap(out, ref) < 1e-5
    counts = [(np.asarray(ids) == e).sum() for e in range(share.held)]
    tiles = np.asarray(cols.tile_group)
    live = int(np.asarray(cols.live_tiles)[0])
    if case == "expert_without_tokens":
        assert counts[2] == 0 and 2 not in tiles[:live]
    elif case == "expert_with_every_token":
        assert counts[1] == 96 and (tiles[:live] == 1).sum() == 1
    else:
        # No group fills its last tile, and the buffer ends in dead tiles,
        # which come out zero.
        assert all(c % BN for c in counts if c)
        assert live == len(tiles) - 2
        assert not np.asarray(coded[:, live * BN:]).any()
    # Every assignment to a held expert has a column holding its token,
    # and padded columns hold none.
    tok, assign = np.asarray(cols.tok), np.asarray(cols.assign)
    held = (np.asarray(ids) >= 0) & (np.asarray(ids) < share.held)
    assert (tok[assign[held]] == np.nonzero(held)[0]).all()
    assert (assign[~held] == len(tok)).all()
    assert (tok < 96).sum() == sum(counts) + N_SHARED * 96 // SHARES


class _Compiles:
    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def test_two_routings_compile_once():
    w = _weights()
    share = _share(0)
    layer = _layer(share, True)
    params = _params(w, share)
    x1, x2 = _x(1), _x(2)
    cols1 = layer(params, x1, 0)[3]
    clock = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(clock)
    _, ids2, _, cols2 = layer(params, x2, 0)
    assert clock.n == 0
    assert cols1.tok.shape == cols2.tok.shape
    ids1 = layer(params, x1, 1)[1]
    assert not np.array_equal(np.asarray(ids1), np.asarray(ids2))
    # The lost shard is an argument, not a new program, and a new survivor
    # set's recoveries run the programs the first one compiled.
    assert clock.n == 0
    assert {name for name, *_ in layer._compiled} == {
        "moe_route", "moe_gate_up", "moe_down", "moe_combine"}
    assert len(layer._compiled) == 4


@pytest.mark.parametrize("cols,bn,want", [
    (1, 512, 512), (512, 512, 512), (513, 512, 1024),
    (35000, 512, 39424), (32768, 512, 32768), (32769, 512, 39424),
    (39425, 512, 46592)])
def test_buffer_columns_ladder(cols, bn, want):
    got = CM.buffer_columns(cols, bn)
    assert got == want and got % bn == 0 and got >= cols
    assert got < 1.19 * cols + bn


@pytest.mark.parametrize("tokens,top_k,n_experts,want", [
    (32768, 6, 64, 1024), (8192, 6, 64, 256), (64, 4, 16, 128),
    (1 << 20, 8, 8, 1024)])
def test_group_tile_follows_the_mean_load(tokens, top_k, n_experts, want):
    assert CM.group_tile(tokens, top_k, n_experts) == want
