"""A ``ServiceFleet`` builds its joint-state tables (the matrix-M grid and
the per-service index and service-cost tables of ``joint_scenario``) once
and keeps them: later calls reuse them, and what they price is bitwise what
a fresh fleet prices."""
import numpy as np

import jax

from repro.core import scenarios as S
from repro.core import services as SV
from repro.core.costs import HostingCosts, ServiceSet

B, N, T, CHUNK = 3, 3, 32, 8


def _fleet():
    sets = [ServiceSet(tuple(HostingCosts.three_level(m, 0.4, 0.35)
                             for m in (5.0 + b, 10.0, 20.0 - b)),
                       capacity=1.0) for b in range(B)]
    return SV.service_fleet(sets, T)


def _scenario(seed):
    k_arr, k_rent = jax.random.split(jax.random.PRNGKey(seed))
    return S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), 0.3, 0.2, 2.0, 0.2, B),
        S.arma_rents(k_rent, 0.35, B))


def _opt(sf, seed):
    return np.asarray(SV.offline_opt_services(
        sf, scenario=_scenario(seed), n_seeds=2, chunk_size=CHUNK,
        checkpointed=True, collect_schedule=False).cost)


def test_joint_tables_are_built_once():
    sf = _fleet()
    assert sf.joint_grid() is sf.joint_grid()
    idx, g_lane = sf.joint_tables()
    assert sf.joint_tables()[0] is idx and sf.joint_tables()[1] is g_lane
    assert idx.shape == g_lane.shape == (B, N, sf.sets[0].J)
    st = sf.sets[1].joint_states()
    np.testing.assert_array_equal(idx[1], st.T)
    np.testing.assert_array_equal(
        g_lane[1, 2], np.asarray(sf.sets[1].services[2].g, np.float32)[
            st[:, 2]])


def test_kept_tables_price_as_a_fresh_fleet():
    sf = _fleet()
    first = [_opt(sf, seed) for seed in (1, 2)]
    assert np.array_equal(first[1], _opt(_fleet(), 2))
    assert np.array_equal(first[0], _opt(_fleet(), 1))
