"""Compile the engine's hot programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology that the installed TPU compiler describes without the hardware,
so what Mosaic or XLA:TPU would refuse (block shapes, VMEM, HBM) fails
here.  The row-blocked DP forward is compiled with ``interpret=False``
in the rows-minor form the fleet engine gives it, at every frontier width
it may take; the XLA programs at a fleet width of B = 4096 and a chunk of
4096 slots.  A lowering for the chip also shows which frontier widths
take the row-blocked kernel.  The topology is described inside a fixture,
never at import time, and every compile runs in the test's own process.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costs import HostingCosts
from repro.core.policies.alpha_rr import (alpha_rr_init, alpha_rr_params,
                                          alpha_rr_step)
from repro.core.policies.offline_opt import (DP_ROWS_TRACES, dp_fwd_chunk,
                                             dp_fwd_frontier)
from repro.core.simulator import sim_acc0, sim_chunk_core
from repro.kernels.hosting import dp_minplus_rows

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


@pytest.mark.parametrize("K", [8, 16, 32, 50, 64])
def test_dp_minplus_rows_compiles_at_the_joint_width(one_chip, K):
    """The row-blocked forward at the multi-tenant cell's size (16384 rows,
    J = 50 joint states, a chunk of 512 slots), and at that size for every
    width from ``DP_ROWS_MIN_K`` = 8 to ``DP_ROWS_MAX_K`` = 64."""
    R, chunk = 16384, 512
    f32 = jnp.float32
    kernel = functools.partial(dp_minplus_rows, interpret=False)
    compiled = jax.jit(kernel).lower(
        _sds(one_chip, (K, R), f32), _sds(one_chip, (chunk, K, R), f32),
        _sds(one_chip, (K, K, R), f32),
        _sds(one_chip, (R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("K", [3, 7, 8, 50, 64, 65])
def test_frontier_forward_takes_the_rows_kernel_by_width(one_chip, K):
    """Lowered for the chip, the batched frontier-only forward runs
    ``dp_minplus_rows`` from K = 8 to 64 (J = 50 is the joint cell's) and
    counts the trace; at K = 3, 7 and 65 it keeps the scan and counts
    nothing."""
    B, chunk = 384, 40                      # shapes no other test traces
    f32 = jnp.float32
    step = jax.vmap(lambda *a: dp_fwd_frontier(*a),
                    in_axes=(0, None, 0, 0, 0, 0, 0, 0))
    before = DP_ROWS_TRACES[(K, chunk)]
    compiled = jax.jit(step).lower(
        _sds(one_chip, (B, K), f32), _sds(one_chip, (chunk,), jnp.int32),
        _sds(one_chip, (B, chunk), f32), _sds(one_chip, (B, chunk, K), f32),
        _sds(one_chip, (B, K), f32), _sds(one_chip, (B, K), jnp.bool_),
        _sds(one_chip, (B, K, K), f32),
        _sds(one_chip, (B,), jnp.int32)).compile()
    rows = K in (8, 50, 64)
    assert ("tpu_custom_call" in compiled.as_text()) == rows
    assert DP_ROWS_TRACES[(K, chunk)] - before == int(rows)


def test_dp_fwd_chunk_xla_fits_one_chip(one_chip):
    B, K = 4096, 8
    f32 = jnp.float32
    step = jax.vmap(dp_fwd_chunk, in_axes=(0, None, 0, 0, 0, 0, 0, 0))
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
