"""The main path's Pallas kernels compile for a TPU v5e, with no chip.

Each case lowers a kernel at the widths the chip smoke run uses against a
described ``v5e:2x2`` topology and compiles it with the TPU compiler that
ships with jaxlib.  That catches what interpret mode cannot: tiles not
aligned to the TPU layout, VMEM over budget, unpartitionable kernels.  A
compile that passes is not a chip run; nothing here executes.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import coded_matmul, fountain
from repro.kernels.coded_matmul import coded_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lt_decode import lt_decode_round_pallas
from repro.kernels.lt_encode import lt_encode_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# R=32 source blocks of bm=256 rows, K=8 dense parities of degree 16.
R, K, BM, D_PAR = 32, 8, 256, 16


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_coded_matmul_compiles(one_chip, dtype):
    # whole-K row blocks; the output tile comes from the VMEM rule (bf16:
    # all of n, f32: 512 wide)
    kdim = ndim = 4096
    fn = functools.partial(coded_matmul_pallas, bm=BM)
    _assert_kernel(
        fn,
        _spec((R * BM, kdim), dtype, one_chip),
        _spec((kdim, ndim), dtype, one_chip),
        _spec((R + K, D_PAR), jnp.int32, one_chip),
        _spec((R + K, D_PAR), jnp.float32, one_chip),
    )


# The coded expert layer of Moonlight-16B-A3B (hidden 2048, expert width
# 1408), 8 held and 2 shared experts under one code per product, 1024-wide
# column tiles over a 39,936-column buffer (39 tiles).
MOE_PRODUCTS = {
    # (experts, rows, k, bm, coded blocks, d_max)
    "gate_up": (10, 2 * 1408, 2048, 256, 16, 6),
    "down": (10, 2048, 1408, 128, 24, 8),
}


@pytest.mark.parametrize("product", sorted(MOE_PRODUCTS))
def test_grouped_coded_matmul_compiles_at_moonlight_widths(one_chip,
                                                          product):
    E, rows, kdim, bm, C, d_max = MOE_PRODUCTS[product]
    bn, tiles = 1024, 39
    fn = functools.partial(coded_matmul_pallas, bm=bm, bn=bn)
    _assert_kernel(
        fn,
        _spec((E, rows, kdim), jnp.bfloat16, one_chip),
        _spec((kdim, tiles * bn), jnp.bfloat16, one_chip),
        _spec((C, d_max), jnp.int32, one_chip),
        _spec((C, d_max), jnp.float32, one_chip),
        _spec((tiles,), jnp.int32, one_chip),
        _spec((1,), jnp.int32, one_chip),
    )


def test_lt_encode_compiles(one_chip):
    cols, d_max = 4096, 16
    fn = functools.partial(lt_encode_pallas, bm=BM, bc=512)
    _assert_kernel(
        fn,
        _spec((R * BM, cols), jnp.float32, one_chip),
        _spec((R + K, d_max), jnp.int32, one_chip),
        _spec((R + K, d_max), jnp.float32, one_chip),
    )


def test_lt_decode_round_compiles(one_chip):
    # one peel round of 8 sources over 120 received blocks, 64 sources
    n_rx, n_src, S, d_max, cols = 120, 64, 8, 8, 4096
    fn = functools.partial(lt_decode_round_pallas, bm=BM, bc=512)
    _assert_kernel(
        fn,
        _spec((n_rx * BM, cols), jnp.float32, one_chip),
        _spec((n_src * BM, cols), jnp.float32, one_chip),
        _spec((S,), jnp.int32, one_chip),
        _spec((S, d_max), jnp.int32, one_chip),
        _spec((S, d_max), jnp.float32, one_chip),
        _spec((S,), jnp.float32, one_chip),
    )


def test_flash_attention_compiles_at_phi4_mini_widths(one_chip):
    # phi4-mini-3.8b: 24 query heads over 8 KV heads, head_dim 128
    B, Hq, Hkv, T, D = 1, 24, 8, 2048, 128
    fn = functools.partial(flash_attention_pallas, causal=True)
    _assert_kernel(
        fn,
        _spec((B, Hq, T, D), jnp.bfloat16, one_chip),
        _spec((B, Hkv, T, D), jnp.bfloat16, one_chip),
        _spec((B, Hkv, T, D), jnp.bfloat16, one_chip),
    )


def test_sharded_coded_matmul_compiles_on_four_chips(topo):
    # One coded shard per chip over the 'model' axis, as on a 2x2 host.
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                             ("data", "model"))
    plan = coded_matmul.plan_coded_matmul(rows=R * BM, n_shards=4,
                                          overhead=0.5, bm=BM)
    rep = NamedSharding(mesh, P())
    fn = functools.partial(coded_matmul.run, plan, mesh=mesh,
                           use_pallas=True)
    compiled = jax.jit(fn).lower(
        _spec((R * BM, 4096), jnp.bfloat16, rep),
        _spec((4096, 4096), jnp.bfloat16, rep),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings.spec == P("model")


def _decoder_spec(R, width, sharding, dense):
    if dense:
        return fountain.DenseDecoder(_spec((width,), jnp.int32, sharding),
                                     _spec((R, width), jnp.float32, sharding))
    return fountain.SparseDecoder(_spec((R,), jnp.int32, sharding),
                                  _spec((R, width), jnp.int32, sharding),
                                  _spec((R, width), jnp.float32, sharding))


# (coded blocks, rows a block, columns, sources, received blocks, dense):
# the collector's recovery in the phi4 FFN cell (a pseudo-inverse) and the
# Moonlight expert layer's two products (peel matrices).
RECOVERIES = {
    "phi4_ffn": (80, 256, 2048, 64, 70, True),
    "moonlight_gate_up": (16, 256, 39 * 1024, 11, 14, False),
    "moonlight_down": (24, 128, 39 * 1024, 16, 21, False),
}


def _assert_recovery(text, dense):
    """A sparse decoder compiles to the block-combine kernel over the coded
    output as it is (no copy, transpose or gather of the payload); a dense
    one to the gather of the received blocks and one contraction."""
    if dense:
        assert "tpu_custom_call" not in text
        assert text.count(" convolution(") + text.count(" dot(") == 1
        assert " gather(" in text
    else:
        assert "tpu_custom_call" in text
        for op in (" copy(", " transpose(", " gather(", " convolution("):
            assert op not in text, op


@pytest.mark.parametrize("case", sorted(RECOVERIES))
def test_recovery_program_compiles_at_the_cells_shapes(one_chip, case):
    """One program over the coded output viewed as blocks with no copy:
    the kernel for a peel matrix, the contraction for a dense one."""
    nb, bm, n, R_, width, dense = RECOVERIES[case]
    compiled = fountain.apply_decoder.lower(
        _decoder_spec(R_, width, one_chip, dense),
        _spec((nb * bm, n), jnp.bfloat16, one_chip), bm=bm).compile()
    _assert_recovery(compiled.as_text(), dense)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "sparse"])
def test_recovery_program_compiles_on_four_chips(topo, dense):
    """Coded blocks split over the 'model' axis (the phi4 lost-chip cell's
    sizes): one all-gather, the sums on every chip, a replicated result."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                             ("data", "model"))
    split = NamedSharding(mesh, P("model"))
    compiled = fountain.apply_decoder.lower(
        _decoder_spec(64, 72, NamedSharding(mesh, P()), dense),
        _spec((96 * BM, 2048), jnp.bfloat16, split), bm=BM,
        sharding=split).compile()
    text = compiled.as_text()
    assert text.count(" all-gather(") == 1
    _assert_recovery(text, dense)
    assert compiled.output_shardings.spec == P()
