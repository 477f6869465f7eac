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

from repro.core import coded_matmul
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
    kdim = ndim = 4096
    fn = functools.partial(coded_matmul_pallas, bm=BM, bk=256, bn=256)
    _assert_kernel(
        fn,
        _spec((R * BM, kdim), dtype, one_chip),
        _spec((kdim, ndim), dtype, one_chip),
        _spec((R + K, D_PAR), jnp.int32, one_chip),
        _spec((R + K, D_PAR), jnp.float32, one_chip),
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
