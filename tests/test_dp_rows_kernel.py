"""The row-blocked, frontier-only DP forward (``kernels.hosting.
dp_minplus_rows``) against ``jax.vmap`` of the frontier-only ``lax.scan``.

Both must give the same frontier bit for bit: the kernel takes the minimum
of the same float32 sums in another order, and a minimum rounds nothing.
The kernel runs in interpret mode here.  The cases cover a real joint
fetch matrix (J = 50), an odd width, a mixed-J grid with padded ``+inf``
states, a row count that is not a multiple of 128, horizons that end
inside or before the chunk, and integer costs that force ties.  The
kernel is also held, one row per case, to ``test_kernels.py``'s float32
numpy replay of the recursion: the same cases, ties and chained chunks
on which the scan meets it.  The last test runs the whole joint OPT with the row-blocked path switched on
through ``offline_opt.dp_rows_platforms``, and its costs must be bitwise
those of the scan.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fleet as F
from repro.core import scenarios as S
from repro.core import services as SV
from repro.core.costs import HostingCosts, ServiceSet, joint_hosting_grid
from repro.kernels import hosting as H
from test_kernels import (DP_CASES, _dp_case, dp_case_seed, dp_ties_case,
                          np_dp_forward, np_dp_holding)

O = importlib.import_module("repro.core.policies.offline_opt")


def _sset(Ms, capacity):
    return ServiceSet(tuple(HostingCosts.three_level(m, 0.4, 0.35)
                            for m in Ms), capacity=capacity)


def _scan(J, w, fetch, n_live):
    """``jax.vmap`` of the frontier-only scan on holding costs ``w``
    [R, chunk, K] (``+inf`` on masked states).  With unit rents and zero
    levels the scan's own holding costs are ``1 * 0 + w``, which is ``w``
    exactly."""
    R, chunk, K = w.shape
    step = jax.jit(jax.vmap(O._dp_fwd_scan,
                            in_axes=(0, None, 0, 0, 0, 0, 0, 0)))
    return np.asarray(step(J, jnp.arange(chunk, dtype=jnp.int32),
                           jnp.ones((R, chunk), jnp.float32), w,
                           jnp.zeros((R, K), jnp.float32),
                           jnp.ones((R, K), bool), fetch, n_live))


def _rows(J, w, fetch, n_live):
    out = H.dp_minplus_rows(jnp.asarray(J).T,
                            jnp.transpose(jnp.asarray(w), (1, 2, 0)),
                            jnp.transpose(jnp.asarray(fetch), (1, 2, 0)),
                            n_live, interpret=True)
    return np.asarray(out).T


def _frontiers(rng, R, K, reachable=None):
    """Entry frontiers: finite on reachable states, ``+inf`` elsewhere and
    on some states besides, as a DP frontier has."""
    J = rng.uniform(0.0, 40.0, (R, K)).astype(np.float32)
    J[rng.uniform(size=(R, K)) < 0.2] = np.inf
    J[:, 0] = rng.uniform(0.0, 5.0, R).astype(np.float32)
    if reachable is not None:
        J[~reachable] = np.inf
    return J


def _check(J, w, fetch, n_live):
    n_live = jnp.asarray(n_live, jnp.int32)
    ref = _scan(J, w, fetch, n_live)
    out = _rows(J, w, fetch, n_live)
    assert np.isfinite(ref).any()
    np.testing.assert_array_equal(out, ref)


def _oracle_rows(J0, tids, cck, sck, lv32, kmask, fetch, T_len):
    """One DP case of ``test_kernels.py`` as one row of ``_rows``: the
    holding costs the oracle prices, and the slots live from ``tids[0]``
    on."""
    return (np.asarray(J0)[None], np_dp_holding(cck, sck, lv32, kmask)[None],
            np.asarray(fetch)[None],
            jnp.asarray([int(T_len) - int(tids[0])], jnp.int32))


@pytest.mark.parametrize("chunk,K,k_used,T_len", DP_CASES)
def test_rows_kernel_matches_numpy_oracle(chunk, K, k_used, T_len):
    """Masked levels, frozen tails and a fully frozen chunk, as the scan
    meets them in ``test_kernels.py``."""
    case = _dp_case(dp_case_seed(chunk, K), chunk, K, k_used, T_len)
    out = _rows(*_oracle_rows(*case))
    np.testing.assert_array_equal(out[0], np_dp_forward(*case)[0])


def test_rows_kernel_ties_match_numpy_oracle():
    """Every predecessor tied (a zero fetch matrix)."""
    case = dp_ties_case()
    out = _rows(*_oracle_rows(*case))
    np.testing.assert_array_equal(out[0], np_dp_forward(*case)[0])


def test_rows_kernel_chained_chunks_match():
    """Two 16-slot calls, the frontier carried from one to the next, equal
    one 32-slot call and the oracle, with the horizon ending inside the
    second chunk."""
    J0, tids, cck, sck, lv32, kmask, fetch, _ = _dp_case(3, 32, 5, 4, 32)
    T_len = jnp.int32(27)
    whole = _rows(*_oracle_rows(J0, tids, cck, sck, lv32, kmask, fetch,
                                T_len))
    J = np.asarray(J0)
    for t0 in (0, 16):
        cut = slice(t0, t0 + 16)
        J = _rows(*_oracle_rows(J, tids[cut], cck[cut], sck[cut], lv32,
                                kmask, fetch, T_len))[0]
    np.testing.assert_array_equal(J, whole[0])
    np.testing.assert_array_equal(
        J, np_dp_forward(J0, tids, cck, sck, lv32, kmask, fetch, T_len)[0])


def test_real_joint_fetch_matrices_J50():
    rng = np.random.default_rng(0)
    sets = [_sset(Ms, 2.0) for Ms in ((5.0, 10.0, 20.0, 10.0),
                                      (20.0, 5.0, 5.0, 10.0),
                                      (10.0, 10.0, 10.0, 10.0))]
    assert {ss.J for ss in sets} == {50}
    R, chunk = 130, 24
    pick = rng.integers(0, len(sets), R)
    fetch = np.stack([sets[i].joint_fetch_matrix() for i in pick])
    w = rng.uniform(0.0, 3.0, (R, chunk, 50)).astype(np.float32)
    _check(_frontiers(rng, R, 50), w, fetch, np.full(R, chunk))


def test_odd_width_J7():
    rng = np.random.default_rng(1)
    R, chunk, K = 128, 19, 7
    fetch = rng.uniform(0.0, 10.0, (R, K, K)).astype(np.float32)
    w = rng.uniform(0.0, 3.0, (R, chunk, K)).astype(np.float32)
    _check(_frontiers(rng, R, K), w, fetch, np.full(R, chunk))


def test_mixed_J_grid_with_padded_states():
    rng = np.random.default_rng(2)
    sets = [_sset((5.0, 10.0, 20.0, 10.0), 2.0),
            _sset((10.0, 20.0, 5.0, 5.0), 1.0),
            _sset((20.0, 5.0, 10.0, 10.0), 1.4)]
    grid = joint_hosting_grid(sets)
    mask = np.asarray(grid.mask)
    assert not mask.all() and mask.any(axis=1).all()
    R, chunk, K = 96, 16, mask.shape[1]
    pick = rng.integers(0, len(sets), R)
    fetch = np.asarray(grid.M)[pick]
    w = rng.uniform(0.0, 3.0, (R, chunk, K)).astype(np.float32)
    w = np.where(mask[pick][:, None, :], w, np.inf).astype(np.float32)
    _check(_frontiers(rng, R, K, mask[pick]), w, fetch, np.full(R, chunk))


@pytest.mark.parametrize("R", [1, 37])
def test_rows_not_a_multiple_of_128(R):
    rng = np.random.default_rng(3 + R)
    chunk, K = 12, 9
    fetch = rng.uniform(0.0, 10.0, (R, K, K)).astype(np.float32)
    w = rng.uniform(0.0, 3.0, (R, chunk, K)).astype(np.float32)
    _check(_frontiers(rng, R, K), w, fetch, np.full(R, chunk))


def test_horizons_end_inside_and_before_the_chunk():
    """Slots at or past a row's horizon keep its frontier: horizons end
    before the chunk (negative or zero live slots), inside it, at its end
    and past it."""
    rng = np.random.default_rng(4)
    R, chunk, K = 40, 20, 11
    fetch = rng.uniform(0.0, 10.0, (R, K, K)).astype(np.float32)
    w = rng.uniform(0.0, 3.0, (R, chunk, K)).astype(np.float32)
    n_live = rng.integers(-5, chunk + 6, R)
    n_live[:4] = (-3, 0, chunk, 1)
    J = _frontiers(rng, R, K)
    _check(J, w, fetch, n_live)
    frozen = _rows(J, w, fetch, jnp.asarray(n_live, jnp.int32))[n_live <= 0]
    np.testing.assert_array_equal(frozen, J[n_live <= 0])


def test_slot_tiles_and_a_ragged_last_tile(monkeypatch):
    """A chunk longer than one slot tile, and one that is not a multiple
    of it: the frontier carries across tiles, and padded slots are
    frozen."""
    monkeypatch.setattr(H, "DP_ROWS_W_BLOCK_BYTES", 8 * 8 * 128 * 4)
    assert H.dp_rows_slot_tile(50, 8) == 8
    rng = np.random.default_rng(5)
    R, chunk, K = 20, 50, 8
    fetch = rng.uniform(0.0, 10.0, (R, K, K)).astype(np.float32)
    w = rng.uniform(0.0, 3.0, (R, chunk, K)).astype(np.float32)
    _check(_frontiers(rng, R, K), w, fetch, rng.integers(0, chunk + 3, R))


def test_integer_costs_force_ties():
    rng = np.random.default_rng(6)
    R, chunk, K = 64, 16, 10
    fetch = rng.integers(0, 3, (R, K, K)).astype(np.float32)
    w = rng.integers(0, 3, (R, chunk, K)).astype(np.float32)
    J = rng.integers(0, 4, (R, K)).astype(np.float32)
    _check(J, w, fetch, np.full(R, chunk))


def test_widths_that_take_the_rows_kernel():
    assert O.dp_rows_platforms(3) == ()
    assert O.dp_rows_platforms(O.DP_ROWS_MIN_K - 1) == ()
    assert O.dp_rows_platforms(O.DP_ROWS_MIN_K) == ("tpu",)
    assert O.dp_rows_platforms(50) == ("tpu",)
    assert O.dp_rows_platforms(O.DP_ROWS_MAX_K) == ("tpu",)
    assert O.dp_rows_platforms(O.DP_ROWS_MAX_K + 1) == ()


def test_narrow_frontier_keeps_the_scan_program():
    """Below ``DP_ROWS_MIN_K`` the frontier-only forward is the scan's own
    program, equation for equation."""
    R, chunk, K = 16, 8, 3
    assert O.dp_rows_platforms(K) == ()
    args = (jnp.zeros((R, K)), jnp.arange(chunk, dtype=jnp.int32),
            jnp.zeros((R, chunk)), jnp.zeros((R, chunk, K)),
            jnp.zeros((R, K)), jnp.ones((R, K), bool),
            jnp.zeros((R, K, K)), jnp.zeros((R,), jnp.int32))
    in_axes = (0, None, 0, 0, 0, 0, 0, 0)

    def program(f):
        return str(jax.make_jaxpr(jax.vmap(f, in_axes=in_axes))(*args))

    assert program(O.dp_fwd_frontier) == program(
        lambda *a: O.dp_fwd_chunk(*a)[0])


def test_dispatch_rows_match_the_scan():
    """``dp_fwd_frontier`` under ``jax.vmap``, row-blocked (its holding
    costs built rows-minor inside the batching rule) against the scan, on
    a mixed-J joint grid with rents, levels and per-state service costs."""
    rng = np.random.default_rng(7)
    sets = [_sset((5.0, 10.0, 20.0, 10.0), 2.0),
            _sset((10.0, 20.0, 5.0, 5.0), 1.0)]
    grid = joint_hosting_grid(sets)
    R, chunk, K = 33, 24, grid.mask.shape[1]
    pick = rng.integers(0, len(sets), R)
    args = (_frontiers(rng, R, K, np.asarray(grid.mask)[pick]),
            jnp.arange(chunk, dtype=jnp.int32) + 100,
            rng.uniform(0.07, 1.05, (R, chunk)).astype(np.float32),
            rng.uniform(0.0, 5.0, (R, chunk, K)).astype(np.float32),
            np.asarray(grid.levels)[pick], np.asarray(grid.mask)[pick],
            np.asarray(grid.M)[pick],
            (100 + rng.integers(-4, chunk + 4, R)).astype(np.int32))
    in_axes = (0, None, 0, 0, 0, 0, 0, 0)
    ref = jax.jit(jax.vmap(O._dp_fwd_scan, in_axes=in_axes))(*args)
    before = O.DP_ROWS_TRACES[(K, chunk)]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(O, "dp_rows_platforms", lambda K: ("cpu",))
        out = jax.jit(jax.vmap(O.dp_fwd_frontier, in_axes=in_axes))(*args)
    assert O.DP_ROWS_TRACES[(K, chunk)] == before + 1
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.fixture
def fresh_programs():
    """Programs traced here take the switched dispatch: trace them anew,
    and leave none of them behind for other tests."""
    jax.clear_caches()
    F._compiled_dp_ckpt_scenario_core.cache_clear()
    yield
    jax.clear_caches()
    F._compiled_dp_ckpt_scenario_core.cache_clear()


def test_joint_opt_costs_bitwise_with_the_rows_path(monkeypatch,
                                                    fresh_programs):
    """The joint OPT at its CPU cut (8 sites of 4 services under a cap of
    2.0, J = 50, T = 256, chunk 64, 4 seeds), the row-blocked path forced
    on the CPU: costs bitwise those of the scan."""
    B, T, chunk = 8, 256, 64
    rng = np.random.default_rng(8)
    sets = [_sset(tuple(rng.choice([5.0, 10.0, 20.0], 4)), 2.0)
            for _ in range(B)]
    sf = SV.service_fleet(sets, T)
    k_arr, k_rent = jax.random.split(jax.random.PRNGKey(11))
    sc = S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), 0.3, 0.2, 2.0, 0.2, B),
        S.arma_rents(k_rent, 0.35, B))

    def opt():
        return np.asarray(SV.offline_opt_services(
            sf, scenario=sc, n_seeds=4, chunk_size=chunk, checkpointed=True,
            collect_schedule=False).cost)

    scan = opt()
    calls = []
    kernel = H.dp_minplus_rows

    def spy(*a, **kw):
        calls.append(kw.get("interpret"))
        return kernel(*a, **kw)

    monkeypatch.setattr(H, "dp_minplus_rows", spy)
    monkeypatch.setattr(O, "dp_rows_platforms", lambda K: ("cpu",))
    jax.clear_caches()
    F._compiled_dp_ckpt_scenario_core.cache_clear()
    rows = opt()
    assert calls and all(calls)         # traced in, in interpret mode
    assert np.isfinite(scan).all()
    np.testing.assert_array_equal(rows, scan)
