"""Primitive workload streams (counter-based; see ``base`` for the contract).

Arrival streams: ``bernoulli_arrivals``, ``poisson_arrivals``,
``ge_arrivals`` (Gilbert-Elliot, side = chain state), ``bursty_arrivals``
(the cluster-trace stand-in), ``adversarial_fetch_bait`` /
``adversarial_evict_bait`` (Theorem-4 constructions), ``trace_arrivals``.

Rent streams: ``uniform_rents``, ``na_rents`` (antithetic time-pairs,
Assumption 7), ``arma_rents`` / ``spot_rents`` (ARMA(p,q) spot prices),
``constant_rents``, ``trace_rents``.

Service streams: ``model2_service`` (coupled per-request uniforms, the
``model2_service_matrix`` construction as a stream).

Randomness per slot ``t`` comes from ``fold_in(key, t)`` (plus small salts
for independent sub-draws within a slot), so every stream is invariant to
chunking; the stateful ones (GE chain, ARMA histories) draw their
innovations that way and thread only the recursion through ``gen_state``.

``bernoulli_arrivals`` and ``uniform_rents`` carry a boolean ``flip`` param
(default False) that maps each slot uniform ``u -> 1 - u``: the hook
``combinators.antithetic_pairing`` uses to build negatively-associated
instance pairs from shared keys.

Every stream's ``init_fn``/``chunk_fn`` is a module-level function (or
comes from a small ``lru_cache``d factory keyed on the static config):
constructing the "same" stream twice yields the *same* function objects,
so the identity-keyed compile caches (``base._compiled_gen``, the fleet
engine's scenario cores) hit instead of re-tracing per construction —
the legacy ``arrivals.py``/``rentcosts.py`` wrappers build a fresh Stream
per call and rely on this.

Poisson draws.  ``jax.random.poisson`` draws by Knuth's method below rate
10 and by transformed rejection at or above it, and under ``vmap`` it runs
*both* samplers over every lane before selecting one.  ``poisson_arrivals``
and ``ge_arrivals`` therefore look at their rates when they are built:
when every rate is concrete and below 10 they take Knuth-only chunk
functions (``_poisson_knuth``, bit-identical to ``jax.random.poisson`` on
the same key in that range), else the general ``jax.random.poisson`` path.
``POISSON_SAMPLER_TRACES`` counts the two at trace time.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import default_float_dtype
from repro.core.scenarios.base import (Stream, as_keys, bcast, slot_keys,
                                       slot_uniform)

# Salt for draws that must not collide with any per-slot counter (slot
# counters are the nonnegative slot indices).
_INIT_SALT = 0x7FFFFFFF

# jax.random.poisson draws by Knuth's method below this rate
_KNUTH_MAX_RATE = 10.0

# test hook: trace-time counts of Poisson draws by sampler, "knuth" (the
# Knuth-only sampler) or "general" (jax.random.poisson)
POISSON_SAMPLER_TRACES = collections.Counter()


def _no_state(params):
    return ()


def _zeros_side(x):
    return jnp.zeros(x.shape, jnp.int32)


def _flip(u, flip):
    return jnp.where(flip, 1.0 - u, u)


# ----------------------------------------------------------------------
# Arrival streams.
# ----------------------------------------------------------------------

def _bernoulli_chunk(params, state, tids):
    u = _flip(slot_uniform(params["key"], tids), params["flip"])
    x = (u < params["p"]).astype(jnp.int32)
    return state, (x, _zeros_side(x))


def bernoulli_arrivals(key, p, B: int) -> Stream:
    """Bernoulli(p) arrivals; ``p`` scalar or per-instance [B]."""
    return Stream("bernoulli", "arrivals", _no_state, _bernoulli_chunk,
                  {"key": as_keys(key, B), "p": bcast(p, B, jnp.float32),
                   "flip": jnp.zeros((B,), bool)})


def _poisson_knuth(key, lam):
    """One Poisson(``lam``) draw by Knuth's method, step for step
    ``jax.random.poisson``'s Knuth sampler: equal to
    ``jax.random.poisson(key, lam, ())`` bit for bit on the same key for
    any ``lam < 10`` or NaN, without also running the rejection sampler."""
    lam = jnp.asarray(lam, jnp.float32)

    def cond(carry):
        return carry[2] > -lam

    def body(carry):
        k, rng, log_prod = carry
        rng, sub = jax.random.split(rng)
        u = jax.random.uniform(sub, (), jnp.float32)
        return k + 1, rng, log_prod + jnp.log(u)

    k, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), key, jnp.float32(0.0)))
    return jnp.where(lam == 0, 0, k - 1)


def _poisson_draws(ks, lam, knuth: bool):
    """[chunk] int32 Poisson counts, one per slot key in ``ks``, at a
    shared scalar ``lam`` or per-slot [chunk] rates."""
    lam_axis = 0 if jnp.ndim(lam) else None
    if knuth:
        POISSON_SAMPLER_TRACES["knuth"] += 1
        with jax.named_scope("poisson_knuth"):
            x = jax.vmap(_poisson_knuth, in_axes=(0, lam_axis))(ks, lam)
    else:
        POISSON_SAMPLER_TRACES["general"] += 1
        x = jax.vmap(lambda k, r: jax.random.poisson(k, r, ()),
                     in_axes=(0, lam_axis))(ks, lam)
    return x.astype(jnp.int32)


def _knuth_rates(*rates) -> bool:
    """True when every rate is concrete and below 10 (NaN counts as
    below), so Knuth alone reproduces ``jax.random.poisson``'s draws."""
    for r in rates:
        if isinstance(r, jax.core.Tracer):
            return False
        if np.any(np.asarray(r, np.float32) >= _KNUTH_MAX_RATE):
            return False
    return True


def _poisson_chunk_at(params, tids, knuth: bool):
    x = _poisson_draws(slot_keys(params["key"], tids), params["lam"], knuth)
    return x, _zeros_side(x)


def _poisson_chunk(params, state, tids):
    return state, _poisson_chunk_at(params, tids, False)


def _poisson_chunk_knuth(params, state, tids):
    return state, _poisson_chunk_at(params, tids, True)


def poisson_arrivals(key, lam, B: int) -> Stream:
    """Poisson(lam) arrivals; ``lam`` scalar or per-instance [B].

    A concrete ``lam`` below 10 everywhere selects the Knuth-only sampler
    at construction.  Such a stream must not be given params with a rate
    of 10 or more later: it would still draw Poisson counts, but not
    ``jax.random.poisson``'s bits."""
    chunk = _poisson_chunk_knuth if _knuth_rates(lam) else _poisson_chunk
    return Stream("poisson", "arrivals", _no_state, chunk,
                  {"key": as_keys(key, B),
                   "lam": bcast(lam, B, jnp.float32)})


def _ge_emit(key, tids, rates, emission: str, salt: int):
    """Per-slot emissions at per-slot rates (counter-keyed)."""
    if emission in ("poisson", "poisson_knuth"):
        ks = slot_keys(key, tids)
        ks = jax.vmap(lambda k: jax.random.fold_in(k, salt))(ks)
        return _poisson_draws(ks, rates, emission == "poisson_knuth")
    if emission == "bernoulli":
        # the fold/salt/uniform chain IS slot_uniform's — draw through it
        u = slot_uniform(key, tids, salt=salt)
        return (u < rates).astype(jnp.int32)
    raise ValueError(emission)


def _ge_states(params, state, tids):
    """Advance the 2-state chain over one chunk; returns (s', states
    [chunk])."""
    u = slot_uniform(params["key"], tids, salt=0)

    def step(s, u_t):
        nxt = jnp.where(s == 1,
                        jnp.where(u_t >= params["p_hl"], 1, 0),
                        jnp.where(u_t < params["p_lh"], 1, 0)).astype(jnp.int32)
        return nxt, nxt

    return jax.lax.scan(step, state["s"], u)


def _ge_init(params):
    # start from the stationary distribution (no burn-in artifacts)
    ph = params["p_lh"] / (params["p_lh"] + params["p_hl"])
    u0 = jax.random.uniform(jax.random.fold_in(params["key"], _INIT_SALT))
    return {"s": (u0 < ph).astype(jnp.int32)}


def _ge_chunk(params, state, tids, emission):
    s, states = _ge_states(params, state, tids)
    rates = jnp.where(states == 1, params["rate_h"], params["rate_l"])
    x = _ge_emit(params["key"], tids, rates, emission, salt=1)
    return {"s": s}, (x, states)


def _ge_chunk_poisson(params, state, tids):
    return _ge_chunk(params, state, tids, "poisson")


def _ge_chunk_poisson_knuth(params, state, tids):
    return _ge_chunk(params, state, tids, "poisson_knuth")


def _ge_chunk_bernoulli(params, state, tids):
    return _ge_chunk(params, state, tids, "bernoulli")


def ge_arrivals(key, p_hl, p_lh, rate_h, rate_l, B: int,
                emission: str = "poisson") -> Stream:
    """Gilbert-Elliot Markov-modulated arrivals; ``side`` carries the chain
    state (1 = H), which is what the MDP/ABC baselines observe.

    Poisson emissions with concrete ``rate_h`` and ``rate_l`` below 10
    everywhere select the Knuth-only sampler at construction.  Such a
    stream must not be given params with a rate of 10 or more later: it
    would still draw Poisson counts, but not ``jax.random.poisson``'s
    bits."""
    chunk = {"poisson": _ge_chunk_poisson,
             "bernoulli": _ge_chunk_bernoulli}[emission]
    if emission == "poisson" and _knuth_rates(rate_h, rate_l):
        chunk = _ge_chunk_poisson_knuth
    return Stream(f"ge-{emission}", "arrivals", _ge_init, chunk,
                  {"key": as_keys(key, B),
                   "p_hl": bcast(p_hl, B, jnp.float32),
                   "p_lh": bcast(p_lh, B, jnp.float32),
                   "rate_h": bcast(rate_h, B, jnp.float32),
                   "rate_l": bcast(rate_l, B, jnp.float32)},
                  has_side=True)


# burst-exit rate of the bursty (cluster-trace-like) GE background — public
# so callers computing the process's stationary mean stay in lockstep
BURSTY_EXIT_P = 0.2


@functools.lru_cache(maxsize=None)
def _bursty_chunk_fn(diurnal_period: int):
    def chunk(params, state, tids):
        state, (x, _) = _ge_chunk_poisson(params, state, tids)
        if diurnal_period:
            t = tids.astype(jnp.float32)
            mod = 1.0 + 0.5 * jnp.sin(2 * jnp.pi * t / diurnal_period)
            lam = jnp.maximum(x.astype(jnp.float32) * mod, 0.0)
            x = _ge_emit(params["key"], tids, lam, "poisson", salt=2)
        return state, (x, _zeros_side(x))

    return chunk


def bursty_arrivals(key, B: int, base_rate=2.0, burst_rate=20.0,
                    burst_p=0.05, diurnal_period: int = 0) -> Stream:
    """The cluster-trace stand-in: GE-Poisson bursts over a low-rate
    background, optionally remodulated by a diurnal sinusoid
    (``arrivals.cluster_trace_like``)."""
    ge = ge_arrivals(key, p_hl=BURSTY_EXIT_P, p_lh=burst_p,
                     rate_h=burst_rate, rate_l=base_rate, B=B)
    return Stream("bursty", "arrivals", _ge_init,
                  _bursty_chunk_fn(int(diurnal_period)), ge.params)


def _fetch_bait_chunk(params, state, tids):
    x = (tids < params["tau"]).astype(jnp.int32)
    return state, (x, _zeros_side(x))


def adversarial_fetch_bait(tau, B: int) -> Stream:
    """Arrivals every slot until ``tau``, then silence (Theorem 4)."""
    return Stream("fetch-bait", "arrivals", _no_state, _fetch_bait_chunk,
                  {"tau": bcast(tau, B, jnp.int32)})


def _evict_bait_chunk(params, state, tids):
    lo, hi = params["tau_bar"], params["tau_bar"] + params["tau"]
    x = ((tids >= lo) & (tids < hi)).astype(jnp.int32)
    return state, (x, _zeros_side(x))


def adversarial_evict_bait(tau_bar, tau, B: int) -> Stream:
    """Silence until ``tau_bar``, arrivals for ``tau`` slots, silence."""
    return Stream("evict-bait", "arrivals", _no_state, _evict_bait_chunk,
                  {"tau_bar": bcast(tau_bar, B, jnp.int32),
                   "tau": bcast(tau, B, jnp.int32)})


def _slice_trace(trace, tids):
    # clipped gather, NOT dynamic_slice: when the engine pads the horizon to
    # a chunk multiple the tail tids overrun the trace, and dynamic_slice
    # would clamp the *start* and shift the whole window.  Clipped indices
    # repeat the last sample on (invalid, masked-out) tail slots and keep
    # the values a pure function of tids — chunk-decomposition invariant.
    return jnp.take(trace, jnp.minimum(tids, trace.shape[0] - 1), axis=0)


def _trace_arrivals_chunk(params, state, tids):
    x = _slice_trace(params["trace"], tids).astype(jnp.int32)
    side = _slice_trace(params["side"], tids).astype(jnp.int32)
    return state, (x, side)


def _trace_arrivals_chunk_sideless(params, state, tids):
    x = _slice_trace(params["trace"], tids).astype(jnp.int32)
    return state, (x, _zeros_side(x))


def trace_arrivals(x, B: Optional[int] = None, side=None) -> Stream:
    """Deterministic playback of a recorded [T] / [B, T] arrival trace.

    The trace rides in params (resident on device), so playback keeps the
    fused-scan plumbing but not the O(B * chunk) memory bound — it is the
    bridge for real traces, not a synthetic generator.  Without ``side``,
    the zeros side channel is emitted per chunk, not stored as a second
    [B, T] trace.
    """
    x = jnp.asarray(x, jnp.int32)
    if x.ndim == 1:
        x = jnp.broadcast_to(x[None, :], (B or 1, x.shape[0]))
    if side is None:
        return Stream("trace", "arrivals", _no_state,
                      _trace_arrivals_chunk_sideless, {"trace": x})
    side = jnp.broadcast_to(jnp.asarray(side, jnp.int32), x.shape)
    return Stream("trace", "arrivals", _no_state, _trace_arrivals_chunk,
                  {"trace": x, "side": side}, has_side=True)


# ----------------------------------------------------------------------
# Rent streams.
# ----------------------------------------------------------------------

def _uniform_rents_chunk(params, state, tids):
    dt = params["lo"].dtype
    u = _flip(slot_uniform(params["key"], tids, dtype=dt), params["flip"])
    c = params["lo"] + u * (params["hi"] - params["lo"])
    return state, c


def uniform_rents(key, c_mean, half_width, B: int, c_min=1e-3) -> Stream:
    """i.i.d. U[c_mean - hw, c_mean + hw] rents (lower-clamped at c_min)."""
    dt = default_float_dtype()
    mean = bcast(c_mean, B, dt)
    hw = bcast(half_width, B, dt)
    return Stream("uniform", "rents", _no_state, _uniform_rents_chunk,
                  {"key": as_keys(key, B),
                   "lo": jnp.maximum(mean - hw, bcast(c_min, B, dt)),
                   "hi": mean + hw,
                   "flip": jnp.zeros((B,), bool)})


def _na_rents_chunk(params, state, tids):
    dt = params["lo"].dtype
    # antithetic time-pairs: slots (2m, 2m+1) share the pair counter m and
    # see (u_m, 1 - u_m) — negatively associated (Assumption 7)
    m = tids // 2
    u = slot_uniform(params["key"], m, dtype=dt)
    v = jnp.where(tids % 2 == 0, u, 1.0 - u)
    return state, params["lo"] + v * (params["hi"] - params["lo"])


def na_rents(key, c_mean, half_width, B: int) -> Stream:
    """Negatively-associated rents via antithetic (U, 1-U) time-pairs."""
    dt = default_float_dtype()
    mean = bcast(c_mean, B, dt)
    hw = bcast(half_width, B, dt)
    return Stream("na-pairs", "rents", _no_state, _na_rents_chunk,
                  {"key": as_keys(key, B), "lo": mean - hw, "hi": mean + hw})


def _constant_rents_chunk(params, state, tids):
    return state, jnp.broadcast_to(params["c"], tids.shape)


def constant_rents(c, B: int) -> Stream:
    return Stream("constant", "rents", _no_state, _constant_rents_chunk,
                  {"c": bcast(c, B, default_float_dtype())})


def _trace_rents_chunk(params, state, tids):
    return state, _slice_trace(params["trace"], tids)


def trace_rents(c, B: Optional[int] = None) -> Stream:
    """Deterministic playback of a recorded rent trace."""
    c = jnp.asarray(c, default_float_dtype())
    if c.ndim == 1:
        c = jnp.broadcast_to(c[None, :], (B or 1, c.shape[0]))
    return Stream("trace", "rents", _no_state, _trace_rents_chunk,
                  {"trace": c})


def _arma_eps_at(params, counters):
    ks = slot_keys(params["key"], counters)
    return params["sigma"] * jax.vmap(
        lambda k: jax.random.normal(k, (), jnp.float32))(ks)


def _arma_init(params):
    p = params["phi"].shape[-1]
    q = params["th"].shape[-1]
    # eps_hist holds (eps_{-1}, ..., eps_{-q}): counters q-1 .. 0
    eps0 = _arma_eps_at(params, jnp.arange(q - 1, -1, -1, dtype=jnp.int32))
    return {"hist": jnp.zeros((p,), jnp.float32), "eps": eps0}


def _arma_chunk(params, state, tids):
    q = params["th"].shape[-1]
    eps = _arma_eps_at(params, tids + q)

    def step(carry, e_t):
        hist, eps_hist = carry
        dev = (jnp.dot(params["phi"], hist) + e_t
               + jnp.dot(params["th"], eps_hist))
        hist = jnp.concatenate([dev[None], hist[:-1]])
        eps_hist = jnp.concatenate([e_t[None], eps_hist[:-1]])
        return (hist, eps_hist), dev

    (hist, eps_hist), devs = jax.lax.scan(step, (state["hist"],
                                                 state["eps"]), eps)
    c = jnp.clip(params["mean"] + devs, params["c_min"], params["c_max"])
    return ({"hist": hist, "eps": eps_hist},
            c.astype(default_float_dtype()))


def arma_rents(key, mean, B: int, ar=None, ma=None, sigma=0.05,
               c_min=0.05, c_max=10.0) -> Stream:
    """ARMA(p, q) rents, clipped to Assumption-3 bounds.

    The AR/MA recursion state (last p deviations, last q innovations) rides
    in ``gen_state``; innovation ``eps_t`` uses counter ``t + q`` (counters
    [0, q) seed the pre-horizon innovations in ``init_fn``), so any chunking
    replays the identical series.  ``ar`` / ``ma`` are per-family tuples
    (static lengths); all coefficients may be per-instance [B, p] / [B, q].
    """
    from repro.core.rentcosts import DEFAULT_AR, DEFAULT_MA
    ar = DEFAULT_AR if ar is None else ar
    ma = DEFAULT_MA if ma is None else ma
    phi = jnp.asarray(ar, jnp.float32)
    th = jnp.asarray(ma, jnp.float32)
    if phi.ndim == 1:
        phi = jnp.broadcast_to(phi[None], (B,) + phi.shape)
    if th.ndim == 1:
        th = jnp.broadcast_to(th[None], (B,) + th.shape)
    return Stream("arma", "rents", _arma_init, _arma_chunk,
                  {"key": as_keys(key, B), "mean": bcast(mean, B, jnp.float32),
                   "phi": phi, "th": th,
                   "sigma": bcast(sigma, B, jnp.float32),
                   "c_min": bcast(c_min, B, jnp.float32),
                   "c_max": bcast(c_max, B, jnp.float32)})


def spot_rents(key, c_mean, B: int, rel_sigma=0.15, c_min=None,
               c_max=None) -> Stream:
    """AWS-spot-like rents: default ARMA(4,2) scaled to a target mean, the
    stream form of ``rentcosts.aws_spot_like`` (same default clip bounds —
    figure modules can therefore set ``HostingCosts`` c_min/c_max a priori
    instead of from the realized trace)."""
    c_mean = np.asarray(c_mean, np.float64)
    return arma_rents(
        key, c_mean, B, sigma=rel_sigma * c_mean,
        c_min=np.maximum(0.2 * c_mean, 1e-3) if c_min is None else c_min,
        c_max=3.0 * c_mean if c_max is None else c_max)


def spot_bounds(c_mean):
    """(c_min, c_max) a ``spot_rents`` stream can ever emit (clip rails)."""
    return float(max(0.2 * c_mean, 1e-3)), float(3.0 * c_mean)


# ----------------------------------------------------------------------
# Service streams (Model 2).
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _model2_chunk_fn(R: int):
    def chunk(params, state, tids, x):
        ks = slot_keys(params["key"], tids)
        u = jax.vmap(lambda k: jax.random.uniform(k, (R,)))(ks)  # [chunk, R]
        live = jnp.arange(R)[None, :] < x[:, None]               # [chunk, R]
        fwd = u[:, :, None] < params["g"][None, None, :]         # [chunk,R,K]
        svc = jnp.sum(jnp.where(live[:, :, None] & fwd, 1.0, 0.0), axis=1)
        return state, svc.astype(params["g"].dtype)

    return chunk


def model2_service(key, g, B: int, max_per_slot: int) -> Stream:
    """Realized Model-2 service costs, coupled across levels: request i of
    slot t draws one uniform; it is forwarded (cost 1) at level k iff
    ``u < g[k]``.  Same construction as ``simulator.model2_service_matrix``
    but counter-keyed per slot.  ``g`` is [K] or [B, K] (pass ``grid.g`` —
    the endpoint-restricted grid then yields exactly the endpoint-gathered
    service costs on the same uniforms)."""
    g = jnp.asarray(g, default_float_dtype())
    if g.ndim == 1:
        g = jnp.broadcast_to(g[None], (B,) + g.shape)
    return Stream("model2", "svc", _no_state,
                  _model2_chunk_fn(int(max_per_slot)),
                  {"key": as_keys(key, B), "g": g})
