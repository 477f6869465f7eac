"""Tests for the serving engine + CCP dispatcher."""

import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.serve_loop import CCPDispatcher, ServeEngine, init_params


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("mistral-nemo-12b", smoke=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params, max_len=48), cfg


def test_generate_shapes_and_determinism(engine):
    eng, cfg = engine
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, size=(3, 8)).astype(np.int32)
    out1 = eng.generate(prompts, n_new=6)
    out2 = eng.generate(prompts, n_new=6)
    assert out1.shape == (3, 6)
    np.testing.assert_array_equal(out1, out2)
    assert out1.min() >= 0 and out1.max() < cfg.vocab


def test_generate_matches_forward_argmax(engine):
    """First generated token == argmax of the full forward pass."""
    eng, cfg = engine
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab, size=(2, 8)).astype(np.int32)
    out = eng.generate(prompts, n_new=1)
    import jax.numpy as jnp

    logits = eng.model.forward(eng.params, jnp.asarray(prompts))
    expect = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
    np.testing.assert_array_equal(out[:, 0], expect)


def test_generate_logits_match_teacher_forced_forward(engine):
    """The logits each greedy token was picked from equal an uncached
    forward over prompt + generated tokens at the same positions."""
    eng, cfg = engine
    prompts = np.random.default_rng(3).integers(
        0, cfg.vocab, size=(2, 8)).astype(np.int32)
    out, logits = eng.generate(prompts, n_new=5, return_logits=True)
    assert logits.shape == (2, 5, cfg.vocab)
    np.testing.assert_array_equal(out, eng.generate(prompts, n_new=5))
    np.testing.assert_array_equal(out, np.asarray(logits.argmax(-1)))
    full = np.concatenate([prompts, out], axis=1)
    ref = eng.model.forward(eng.params, full)[:, 7:12]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_init_params_matches_eager_init():
    """The jitted init draws the same params as ``model.init``."""
    cfg = get_config("phi4-mini-3.8b", smoke=True, param_dtype="bfloat16",
                     compute_dtype="bfloat16")
    model = build_model(cfg)
    eager, _ = model.init(jax.random.PRNGKey(4))
    jitted = init_params(model, seed=4)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_dispatcher_shifts_load_to_fast_replica(engine):
    eng, cfg = engine
    rng = np.random.default_rng(2)
    batches = [rng.integers(0, cfg.vocab, size=(2, 8)).astype(np.int32)
               for _ in range(16)]

    def fast(b):
        return eng.generate(b, n_new=2)

    def slow(b):
        time.sleep(0.05)
        return eng.generate(b, n_new=2)

    disp = CCPDispatcher([fast, slow])
    results, allocs = disp.run(batches)
    assert all(r is not None and r.shape == (2, 2) for r in results)
    if len(allocs) >= 2:
        last = allocs[-1]
        assert last[0] >= last[1], f"fast replica must get >= share: {allocs}"
