"""Plain reference of the multi-tenant edge's joint optimum, independent of
``repro``.

Written from the shared-capacity model of arXiv 2107.10446: N services share
one edge node's storage, the sum of the fractions hosted is capped, each
service sees its own arrival stream and the edge pays one rent.  NumPy over
a handful of rows at a time, one Python loop trip per slot.  It imports
nothing of the program; the draws follow the documented stream conventions
through ``bench.plainref``'s helpers.

Keys: the job's base key splits into an arrival and a rent key, each splits
once per site.  Service n of site b draws its arrivals from
``fold_in(site arrival key, n)``, folded before the Monte-Carlo seed fold,
so row ``b * S + s`` of service n draws from ``fold_in(fold_in(key, n),
s)``.  All services of a site share the site's rent stream
``fold_in(site rent key, s)``.

Joint states are the tuples of per-service level indices whose float64 sum
of hosted fractions is at most the capacity, all-off first.  Per slot t and
joint state j (per-service levels ``lv_n[j]``, service costs ``g_n[j]``):

* holding ``w_t(j) = c_t * sum_n lv_n[j] + sum_n g_n[j] * x_{n,t}``;
* fetch from j' to j ``F[j', j] = sum_n M_n * (lv_n[j] - lv_n[j'])^+``;
* ``V_t(j) = min_j' [V_{t-1}(j') + F[j', j]] + w_t(j)`` from
  ``V_0 = (0, inf, ...)`` at the all-off state; the cost is ``min_j V_T``.

``dt`` is the arithmetic precision: ``np.float64`` for the reference and
``ml_dtypes.bfloat16`` for the control that must fail the comparison.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench import plainref


def row_keys(base_key, n_sites: int, n_seeds: int, n_services: int, rows):
    """``(arrival keys [R, N, 2], rent keys [R, 2])`` of ``rows`` (``b * S
    + s``) of a job whose base key is ``base_key``."""
    import jax
    import jax.numpy as jnp

    rows = np.asarray(rows)
    k_arr, k_rent = jax.random.split(jnp.asarray(base_key, jnp.uint32))
    site = jnp.asarray(rows // n_seeds)
    seed = jnp.asarray(rows % n_seeds, jnp.int32)
    fold = jax.vmap(jax.random.fold_in)
    site_arr = jax.random.split(k_arr, n_sites)[site]             # [R, 2]
    arr = jnp.stack([fold(fold(site_arr, jnp.full_like(seed, n)), seed)
                     for n in range(n_services)], axis=1)         # [R, N, 2]
    rent = fold(jax.random.split(k_rent, n_sites)[site], seed)
    return arr, rent


def observations(base_key, n_sites, n_seeds, n_services, rows, T, arrivals,
                 rents, dt=np.float64):
    """Arrivals x [R, N, T] (int) and the shared rents c [R, T] (``dt``) of
    ``rows`` of a job, from the config's ``arrivals`` (Gilbert-Elliott) and
    ``rents`` (ARMA spot) blocks."""
    ar, ma = rents["ar"], rents["ma"]
    ka, kr = row_keys(base_key, n_sites, n_seeds, n_services, rows)
    R, N = ka.shape[0], n_services
    ka = ka.reshape(R * N, 2)
    u0, u, z = plainref._draws(ka, np.repeat(np.asarray(kr), N, axis=0), T,
                               len(ma))
    s = plainref.ge_chain(u0, u, arrivals["p_hl"], arrivals["p_lh"], dt)
    rates = np.where(s == 1, dt(arrivals["rate_h"]), dt(arrivals["rate_l"]))
    x = np.asarray(plainref._poisson(ka, rates.astype(np.float32)))
    x = x.astype(np.int64).reshape(R, N, T)
    mean = rents["mean"]
    z0 = np.asarray(z).reshape(R, N, -1)[:, 0]        # the one rent stream
    c = plainref.arma(z0, mean, ar, ma, rents["rel_sigma"] * mean,
                      max(rents["min_frac"] * mean, 1e-3),
                      rents["max_frac"] * mean, dt)
    return x, c


def joint_states(lv, capacity: float) -> np.ndarray:
    """[J, N] per-service level indices of every feasible tuple of the
    per-service level lists ``lv`` ([N] lists), row-major, all-off first."""
    tuples = itertools.product(*(range(len(v)) for v in lv))
    return np.array([t for t in tuples
                     if sum(float(lv[n][k]) for n, k in enumerate(t))
                     <= capacity], np.int64)


def joint_opt(x, c, M, lv, g, capacity: float, dt=np.float64):
    """Exact joint optimum [R] of rows with arrivals x [R, N, T], rents
    c [R, T], fetch costs M [R, N] and per-service levels ``lv`` and
    service costs ``g`` ([N, K] each, shared by the rows)."""
    x = np.asarray(x)
    c = np.asarray(c).astype(dt)
    lv = np.asarray(lv, np.float64)
    g = np.asarray(g, np.float64)
    M = np.asarray(M, np.float64).astype(dt)
    st = joint_states(lv, capacity)                             # [J, N]
    N = st.shape[1]
    lvs = np.stack([lv[n][st[:, n]] for n in range(N)]).astype(dt)  # [N, J]
    gs = np.stack([g[n][st[:, n]] for n in range(N)]).astype(dt)    # [N, J]
    L = np.sum(lvs, axis=0, dtype=dt)                                # [J]
    up = np.maximum(lvs[:, None, :] - lvs[:, :, None], dt(0))   # [N, J', J]
    F = sum(M[:, n, None, None] * up[n] for n in range(N)).astype(dt)
    R, T = c.shape
    V = np.full((R, st.shape[0]), dt(np.inf), dt)
    V[:, 0] = dt(0)
    for t in range(T):
        xt = x[:, :, t].astype(dt)
        svc = sum(xt[:, n, None] * gs[n] for n in range(N)).astype(dt)
        w = (c[:, t, None] * L[None, :] + svc).astype(dt)
        V = (np.min(V[:, :, None] + F, axis=1) + w).astype(dt)
    return np.min(V, axis=1)
