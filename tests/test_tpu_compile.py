"""Compile the engine's hot programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology that the installed TPU compiler describes without the hardware,
so what Mosaic or XLA:TPU would refuse (block shapes, VMEM, HBM) fails
here.  The kernels are compiled with ``interpret=False`` in the vmapped
form the fleet engine runs; the XLA programs at the fleet width the chip
smoke run uses (B = 4096, chunk = 4096).  The topology is described inside
a fixture, never at import time, and every compile runs in the test's own
process.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costs import HostingCosts
from repro.core.policies.alpha_rr import (alpha_rr_init, alpha_rr_params,
                                          alpha_rr_step)
from repro.core.policies.offline_opt import dp_fwd_chunk
from repro.core.simulator import sim_acc0, sim_chunk_core
from repro.kernels.hosting import dp_minplus_kc, slot_uniform_tc

HBM_BYTES = 16 * 10**9          # one v5e chip
CHUNK = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described compile is written to the persistent cache but cannot be
    # read back without the chip: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("K", [3, 8])
def test_dp_minplus_kernel_compiles_vmapped(one_chip, K):
    B = 64
    f32 = jnp.float32
    kernel = functools.partial(dp_minplus_kc, interpret=False)
    compiled = jax.jit(jax.vmap(kernel)).lower(
        _sds(one_chip, (B, K), f32), _sds(one_chip, (B, CHUNK, K), f32),
        _sds(one_chip, (B, K, K), f32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("salt", [None, 1])
def test_slot_uniform_kernel_compiles_vmapped(one_chip, salt):
    B = 64
    kernel = functools.partial(slot_uniform_tc, salt=salt, interpret=False)
    compiled = jax.jit(jax.vmap(kernel, in_axes=(0, None))).lower(
        _sds(one_chip, (B, 2), jnp.uint32),
        _sds(one_chip, (CHUNK,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dp_fwd_chunk_xla_fits_one_chip(one_chip):
    B, K = 4096, 8
    f32 = jnp.float32
    step = jax.vmap(functools.partial(dp_fwd_chunk, backend="xla"),
                    in_axes=(0, None, 0, 0, 0, 0, 0, 0))
    compiled = jax.jit(step).lower(
        _sds(one_chip, (B, K), f32), _sds(one_chip, (CHUNK,), jnp.int32),
        _sds(one_chip, (B, CHUNK), f32), _sds(one_chip, (B, CHUNK, K), f32),
        _sds(one_chip, (B, K), f32), _sds(one_chip, (B, K), jnp.bool_),
        _sds(one_chip, (B, K, K), f32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    _fits(compiled)


def test_alpha_rr_sim_chunk_fits_one_chip(one_chip):
    B = 4096
    costs = HostingCosts.three_level(10.0, 0.4, 0.35)
    K = costs.K
    f32 = jnp.float32
    params = alpha_rr_params(costs)
    carry = jax.eval_shape(
        lambda p: (alpha_rr_init(p), sim_acc0(K, f32)), params)

    def batched(a):
        return _sds(one_chip, (B,) + tuple(a.shape), a.dtype)

    core = functools.partial(sim_chunk_core, alpha_rr_step, True)
    compiled = jax.jit(jax.vmap(
        core, in_axes=(0, 0, 0, 0, None, 0, 0, 0, 0, 0))).lower(
        jax.tree_util.tree_map(batched, params),
        _sds(one_chip, (B, K), f32), _sds(one_chip, (B,), f32),
        _sds(one_chip, (B,), jnp.int32), _sds(one_chip, (), jnp.int32),
        jax.tree_util.tree_map(batched, carry),
        _sds(one_chip, (B, CHUNK), jnp.int32),
        _sds(one_chip, (B, CHUNK), f32), _sds(one_chip, (B, CHUNK, K), f32),
        _sds(one_chip, (B, CHUNK), jnp.int32)).compile()
    _fits(compiled)
