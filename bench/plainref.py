"""Plain reference of the hosting engine's semantics, independent of ``repro``.

Written from the paper (arXiv 2103.00555, Sections 2.5-2.6 and Algorithm 1)
and the documented workload conventions, in NumPy over a handful of rows at
a time, one Python loop trip per slot.  It imports nothing of the program
and takes nothing the program made: observations are drawn here from the
same seed-derived keys the benchmark hands to the program, following the
documented stream conventions (counter keys ``fold_in(key, t)``, salts, the
Monte-Carlo seed folded first).

``dt`` is the arithmetic precision: ``np.float64`` for the reference, and
``ml_dtypes.bfloat16`` for the control that must fail the comparison.

Cost model per slot t holding level r_t and choosing r_{t+1}:
rent ``c_t * lv[r_t]`` + service ``g[r_t] * x_t`` + fetch
``M * (lv[r_{t+1}] - lv[r_t])^+``; every policy starts at level 0, and online
policies also pay the fetch decided at the last slot.
"""
from __future__ import annotations

import numpy as np

# Counter reserved for draws made before the horizon (never a slot index).
INIT_SALT = 0x7FFFFFFF
# Algorithm 1 breaks ties toward the held level: a candidate must beat it by
# more than this margin.
TIE_EPS = 1e-6


# ----------------------------------------------------------------------
# Observations: Gilbert-Elliott Poisson arrivals and ARMA(p, q) spot rents.
# ----------------------------------------------------------------------

def row_keys(base_key, n_sites: int, n_seeds: int, rows):
    """Per-row ``(arrival keys, rent keys)`` [R, 2] for rows ``b * S + s``
    of a job whose base key is ``base_key``: the base key splits into an
    arrival and a rent key, each splits once per site, and the Monte-Carlo
    seed ``s`` is folded into the site's key."""
    import jax
    import jax.numpy as jnp

    rows = np.asarray(rows)
    k_arr, k_rent = jax.random.split(jnp.asarray(base_key, jnp.uint32))
    site = jnp.asarray(rows // n_seeds)
    seed = jnp.asarray(rows % n_seeds, jnp.int32)
    fold = jax.vmap(jax.random.fold_in)
    return (fold(jax.random.split(k_arr, n_sites)[site], seed),
            fold(jax.random.split(k_rent, n_sites)[site], seed))


def _draws(arr_keys, rent_keys, T: int, q: int):
    """Uniforms of the chain (salt 0), its start, and the rent innovations'
    standard normals at counters 0 .. T + q - 1."""
    import jax
    import jax.numpy as jnp

    t = jnp.arange(T, dtype=jnp.int32)
    n = jnp.arange(T + q, dtype=jnp.int32)

    def per_row(ka, kr):
        slot = jax.vmap(lambda i: jax.random.fold_in(ka, i))(t)
        u = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, 0), (), jnp.float32))(slot)
        u0 = jax.random.uniform(jax.random.fold_in(ka, INIT_SALT), (),
                                jnp.float32)
        z = jax.vmap(lambda i: jax.random.normal(
            jax.random.fold_in(kr, i), (), jnp.float32))(n)
        return u0, u, z

    return jax.jit(jax.vmap(per_row))(arr_keys, rent_keys)


def _poisson(arr_keys, rates):
    """Poisson counts at per-slot rates, one draw per slot from
    ``fold_in(fold_in(key, t), 1)``."""
    import jax
    import jax.numpy as jnp

    def per_row(ka, lam):
        t = jnp.arange(lam.shape[0], dtype=jnp.int32)
        return jax.vmap(lambda i, r: jax.random.poisson(
            jax.random.fold_in(jax.random.fold_in(ka, i), 1), r, ()))(t, lam)

    return jax.jit(jax.vmap(per_row))(arr_keys, rates)


def ge_chain(u0, u, p_hl, p_lh, dt):
    """Gilbert-Elliott states [R, T] (1 = high): start from the stationary
    law, then each slot's uniform moves the chain before it emits."""
    u0 = np.asarray(u0).astype(dt)
    u = np.asarray(u).astype(dt)
    p_hl, p_lh = dt(p_hl), dt(p_lh)
    s = u0 < p_lh / (p_lh + p_hl)
    out = np.empty(u.shape, np.int8)
    for t in range(u.shape[1]):
        s = np.where(s, u[:, t] >= p_hl, u[:, t] < p_lh)
        out[:, t] = s
    return out


def arma(z, mean, ar, ma, sigma, c_min, c_max, dt):
    """ARMA(p, q) rents [R, T]: ``c_t = clip(mean + d_t)`` with
    ``d_t = sum_i ar_i d_{t-i} + e_t + sum_j ma_j e_{t-j}``; innovations are
    ``sigma * z`` with ``e_t`` at counter ``t + q`` (counters below q are the
    innovations before the horizon) and ``d`` zero before it."""
    z = np.asarray(z)
    q, p = len(ma), len(ar)
    e = (np.asarray(z, np.float64).astype(dt) * dt(sigma)).astype(dt)
    R, T = e.shape[0], e.shape[1] - q
    ar = [dt(a) for a in ar]
    ma = [dt(m) for m in ma]
    dev = [np.zeros(R, dt) for _ in range(p)]          # d_{t-1}, ..., d_{t-p}
    c = np.empty((R, T), dt)
    lo, hi, mu = dt(c_min), dt(c_max), dt(mean)
    for t in range(T):
        d = e[:, t + q]
        for i in range(p):
            d = d + ar[i] * dev[i]
        for j in range(q):
            d = d + ma[j] * e[:, t + q - 1 - j]
        dev = [d] + dev[:-1]
        c[:, t] = np.clip(mu + d, lo, hi)
    return c


def observations(base_key, n_sites, n_seeds, rows, T, arrivals, rents,
                 dt=np.float64):
    """Arrivals x [R, T] (int) and rents c [R, T] (``dt``) of ``rows`` of a
    job, from the config's ``arrivals`` (Gilbert-Elliott) and ``rents``
    (ARMA spot) blocks."""
    ar, ma = rents["ar"], rents["ma"]
    ka, kr = row_keys(base_key, n_sites, n_seeds, rows)
    u0, u, z = _draws(ka, kr, T, len(ma))
    s = ge_chain(u0, u, arrivals["p_hl"], arrivals["p_lh"], dt)
    rates = np.where(s == 1, dt(arrivals["rate_h"]), dt(arrivals["rate_l"]))
    x = np.asarray(_poisson(ka, rates.astype(np.float32))).astype(np.int64)
    mean = rents["mean"]
    c = arma(z, mean, ar, ma, rents["rel_sigma"] * mean,
             max(rents["min_frac"] * mean, 1e-3), rents["max_frac"] * mean, dt)
    return x, c


# ----------------------------------------------------------------------
# Policies and the offline optimum, vectorised over rows.
# ----------------------------------------------------------------------

def _sel(a, r):
    """a[i, r[i]] for every row i."""
    return np.take_along_axis(a, r[:, None], axis=1)[:, 0]


def retro_renting(x, c, M, lv, g, dt=np.float64):
    """Algorithm 1 (alpha-RetroRenting) over the level grid ``lv`` [R, K]
    with service costs ``g`` [R, K]; K = 2 is RetroRenting.

    Algorithm 1 switches from the held level r to level j at slot t when,
    over the window since the last switch, holding r and then j from some
    slot onwards, plus the retrospective charge ``M |lv_j - lv_r|``, beats
    holding r throughout.  The best switch point is a minimum suffix sum of
    the per-slot cost differences, kept as ``S_j(t) = d_t + min(0, S_j(t-1))``
    once the window holds two slots.

    Returns ``(rent, service, fetch, levels [R, T])``."""
    x = np.asarray(x)
    c = np.asarray(c).astype(dt)
    M, lv, g = (np.asarray(a, np.float64).astype(dt) for a in (M, lv, g))
    R, T = x.shape
    K = lv.shape[1]
    zero, inf, eps = dt(0), dt(np.inf), dt(TIE_EPS)
    cols = np.arange(K)[None, :]
    r = np.zeros(R, np.int64)
    S = np.full((R, K), inf, dt)
    age = np.zeros(R, np.int64)
    rent, svc, fetch = (np.zeros(R, dt) for _ in range(3))
    levels = np.empty((R, T), np.int8)
    for t in range(T):
        xt = x[:, t].astype(dt)
        w = (c[:, t, None] * lv + g * xt[:, None]).astype(dt)
        lv_r = _sel(lv, r)
        rent = rent + c[:, t] * lv_r
        svc = svc + _sel(g, r) * xt
        age = age + 1
        open_ = (age >= 2)[:, None]
        S = np.where(open_, w - _sel(w, r)[:, None] + np.minimum(zero, S), S)
        held = cols == r[:, None]
        margin = M[:, None] * np.abs(lv - lv_r[:, None]) + np.where(open_, S,
                                                                    inf)
        margin = np.where(held, zero, margin)
        j = np.argmin(np.where(held, margin, margin + eps), axis=1)
        switch = _sel(margin, j) < zero
        r_next = np.where(switch, j, r)
        fetch = fetch + M * np.maximum(_sel(lv, r_next) - lv_r, zero)
        S = np.where(switch[:, None], inf, S)
        age = np.where(switch, 0, age)
        levels[:, t] = r
        r = r_next
    return rent, svc, fetch, levels


def static_level(x, c, M, lv, g, level, dt=np.float64):
    """Hold level index ``level`` [R] from slot 2 on (slot 1 is at level 0,
    and the fetch is paid at the end of slot 1).  Returns
    ``(rent, service, fetch)``."""
    x = np.asarray(x)
    c = np.asarray(c).astype(dt)
    M, lv, g = (np.asarray(a, np.float64).astype(dt) for a in (M, lv, g))
    level = np.asarray(level)
    lv_k, g_k = _sel(lv, level), _sel(g, level)
    rent = lv_k * np.sum(c[:, 1:], axis=1, dtype=dt)
    svc = (x[:, 0].astype(dt)
           + g_k * np.sum(x[:, 1:].astype(dt), axis=1, dtype=dt))
    fetch = M * np.maximum(lv_k - _sel(lv, np.zeros_like(level)), dt(0))
    return rent.astype(dt), svc.astype(dt), fetch.astype(dt)


def offline_opt(x, c, M, lv, g, dt=np.float64):
    """Exact offline optimum: ``J_t(k) = min_k' [J_{t-1}(k') +
    M (lv_k - lv_k')^+] + c_t lv_k + g_k x_t`` from ``J_0 = (0, inf, ...)``;
    the cost is ``min_k J_T(k)`` [R]."""
    x = np.asarray(x)
    c = np.asarray(c).astype(dt)
    M, lv, g = (np.asarray(a, np.float64).astype(dt) for a in (M, lv, g))
    R, T = x.shape
    K = lv.shape[1]
    F = (M[:, None, None]
         * np.maximum(lv[:, None, :] - lv[:, :, None], dt(0))).astype(dt)
    J = np.full((R, K), dt(np.inf), dt)
    J[:, 0] = dt(0)
    for t in range(T):
        w = c[:, t, None] * lv + g * x[:, t, None].astype(dt)
        J = (np.min(J[:, :, None] + F, axis=1) + w).astype(dt)
    return np.min(J, axis=1)


def endpoints(lv, g):
    """The two-level (no partial hosting) grid of the same rows."""
    R = np.asarray(lv).shape[0]
    return np.tile([0.0, 1.0], (R, 1)), np.tile([1.0, 0.0], (R, 1))


def price_lane(lane: str, x, c, M, lv, g, dt=np.float64):
    """Total cost [R] of one named policy lane on rows ``(x, c)``."""
    if lane == "alpha_rr":
        parts = retro_renting(x, c, M, lv, g, dt)[:3]
    elif lane == "rr":
        parts = retro_renting(x, c, M, *endpoints(lv, g), dt)[:3]
    elif lane == "static_bottom":
        parts = static_level(x, c, M, lv, g, np.zeros(len(M), np.int64), dt)
    elif lane == "static_top":
        top = np.full(len(M), np.asarray(lv).shape[1] - 1)
        parts = static_level(x, c, M, lv, g, top, dt)
    else:
        raise ValueError(f"no reference for lane {lane!r}")
    return sum(np.asarray(p, np.float64) for p in parts)
