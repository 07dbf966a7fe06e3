"""Ahead-of-time compiles of the serving path for a described TPU v5e.

Nothing runs here: each test lowers a kernel or jitted step at the size a
deployment holds (2^26 keys, error 64) and compiles it with the TPU
compiler for a ``v5e:2x2`` topology that is described, not attached.  What
Mosaic or XLA would refuse on the chip (unaligned block shapes, too much
VMEM, an unpartitionable collective) fails here at no chip time.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.index.device import sharded_search_a2a, sharded_search_allgather
from repro.index.engine import (_run_on_index, make_plan, pallas_lookup,
                                pallas_search)
from repro.kernels.fitting_lookup import fitting_lookup_pallas

N_KEYS = 1 << 26
ERROR = 64
QCAP = 256
N_SEGMENTS = 1 << 19
BATCH = 4096
# the newest shard of the latest-traffic cell: 15,424 key blocks of 256,
# segments at the full table's density, probed by a whole 16,384-key flush
NEWEST_KEYS = 15424 * 256
NEWEST_SEGMENTS = 1 << 15
FLUSH = 16384


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a described-topology compile is written to the persistent cache but
    # cannot be read back without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _index_arrays(n_keys, n_segments, sharding):
    return (_spec((n_segments,), jnp.float32, sharding),   # seg_start
            _spec((n_segments,), jnp.float32, sharding),   # slope
            _spec((n_segments,), jnp.int32, sharding),     # base
            _spec((n_segments,), jnp.int32, sharding),     # seg_end
            _spec((n_keys,), jnp.float32, sharding))       # keys


@pytest.mark.parametrize("side", ["left", "right"])
def test_fitting_lookup_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                                side):
    plan = make_plan(N_KEYS, ERROR)
    keys = _spec((plan.n_pad,), jnp.float32, one_chip)
    q_b = _spec((plan.n_blocks, QCAP), jnp.float32, one_chip)
    qlo_b = _spec((plan.n_blocks, QCAP), jnp.int32, one_chip)
    fn = functools.partial(fitting_lookup_pallas, kb=plan.kb,
                           window=plan.window, side=side)
    compiled = jax.jit(fn).lower(keys, q_b, qlo_b).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_search_compiles_with_index_arguments(one_chip,
                                                     no_compile_cache):
    arrays = _index_arrays(N_KEYS, N_SEGMENTS, one_chip)
    q = _spec((BATCH,), jnp.float32, one_chip)
    compiled = _run_on_index.lower(
        arrays, q, impl=pallas_search, error=ERROR,
        opts=(("qcap", QCAP), ("side", "left"))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the key column enters as an argument, not as a baked-in constant
    assert compiled.memory_analysis().argument_size_in_bytes >= 4 * N_KEYS


def test_pallas_lookup_compiles_at_the_newest_shard(one_chip,
                                                  no_compile_cache):
    arrays = _index_arrays(NEWEST_KEYS, NEWEST_SEGMENTS, one_chip)
    assert make_plan(NEWEST_KEYS, ERROR).n_blocks == 15424
    q = _spec((FLUSH,), jnp.float32, one_chip)
    compiled = _run_on_index.lower(
        arrays, q, impl=pallas_lookup, error=ERROR,
        opts=(("fallback", True), ("qcap", QCAP))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes >= 4 * NEWEST_KEYS


@pytest.mark.parametrize("exchange", ["allgather", "a2a"])
def test_sharded_search_compiles_on_four_chips(topo, no_compile_cache,
                                               exchange):
    d = 4
    mesh = Mesh(np.asarray(topo.devices[:d]), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    row = NamedSharding(mesh, P("data"))
    repl = NamedSharding(mesh, P())
    m_cap, s_cap = N_KEYS // d * 3 // 2 + 64, N_SEGMENTS // d * 3 // 2 + 8
    table = (_spec((d, s_cap), jnp.float32, rows),
             _spec((d, s_cap), jnp.float32, rows),
             _spec((d, s_cap), jnp.int32, rows),
             _spec((d, s_cap), jnp.int32, rows),
             _spec((d, m_cap), jnp.float32, rows),
             _spec((d,), jnp.int32, row))
    q = _spec((BATCH,), jnp.float32, row)
    if exchange == "allgather":
        fn = functools.partial(sharded_search_allgather, mesh=mesh,
                               error=ERROR)
        args = (*table, q)
    else:
        fn = functools.partial(sharded_search_a2a, mesh=mesh, error=ERROR)
        args = (*table, _spec((d,), jnp.int32, repl),
                _spec((d,), jnp.float32, repl), q)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    collective = "all-gather" if exchange == "allgather" else "all-to-all"
    assert collective in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 16 * 2 ** 30
