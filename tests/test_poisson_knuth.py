"""Knuth-only Poisson arrivals (core/scenarios/streams.py).

* ``_poisson_knuth``, vmapped as the arrival streams vmap it, draws
  ``jax.random.poisson``'s bits on the same keys for every rate below 10
  and for NaN;
* ``ge_arrivals`` / ``poisson_arrivals`` streams at rates below 10 emit
  exactly the counts ``jax.random.poisson`` gives on their slot keys;
* the sampler follows the rates at construction: concrete rates below 10
  take the Knuth-only chunk functions (``POISSON_SAMPLER_TRACES["knuth"]``,
  the ``poisson_knuth`` scope in the compiled program), anything else the
  general ``jax.random.poisson`` path.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scenarios as S
from repro.core.scenarios import streams
from repro.core.scenarios.base import _compiled_gen

KEY = jax.random.PRNGKey(2024)
N_KEYS = 4096
RATES = [0.0, 0.2, 2.0, 9.999, float("nan")]


def _slot_keys(n):
    """[n] per-slot keys salted as ``_ge_emit`` salts its emission keys."""
    ks = S.slot_keys(KEY, jnp.arange(n, dtype=jnp.int32))
    return jax.vmap(lambda k: jax.random.fold_in(k, 1))(ks)


def _jax_poisson(ks, rates):
    return jax.jit(jax.vmap(lambda k, r: jax.random.poisson(k, r, ())))(
        ks, rates)


@pytest.mark.parametrize(
    "rates",
    [np.full(N_KEYS, r, np.float32) for r in RATES]
    + [np.resize(np.asarray(RATES, np.float32), N_KEYS)],
    ids=[f"lam={r}" for r in RATES] + ["mixed"])
def test_knuth_sampler_matches_jax_poisson(rates):
    ks = _slot_keys(N_KEYS)
    got = jax.jit(jax.vmap(streams._poisson_knuth))(ks, jnp.asarray(rates))
    want = _jax_poisson(ks, jnp.asarray(rates))
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(want))


T = 96
B = 4


def _direct_ge(stream, side):
    """GE-Poisson counts drawn straight from ``jax.random.poisson`` on the
    stream's keys: ``fold_in(fold_in(key, t), 1)`` at the chain's rate."""
    p = stream.params
    rates = np.where(np.asarray(side) == 1, np.asarray(p["rate_h"])[:, None],
                     np.asarray(p["rate_l"])[:, None]).astype(np.float32)
    t = jnp.arange(T, dtype=jnp.int32)

    def row(k, lam):
        return jax.vmap(lambda i, r: jax.random.poisson(
            jax.random.fold_in(jax.random.fold_in(k, i), 1), r, ()))(t, lam)

    return jax.jit(jax.vmap(row))(p["key"], jnp.asarray(rates))


def _direct_poisson(stream, side):
    p = stream.params
    t = jnp.arange(T, dtype=jnp.int32)

    def row(k, lam):
        return jax.vmap(lambda i: jax.random.poisson(
            jax.random.fold_in(k, i), lam, ()))(t)

    return jax.jit(jax.vmap(row))(p["key"], p["lam"])


STREAM_CASES = [
    ("ge-poisson", lambda: S.ge_arrivals(S.split_keys(KEY, B), 0.3, 0.2,
                                         2.0, 0.2, B), _direct_ge),
    ("ge-poisson-per-instance",
     lambda: S.ge_arrivals(S.split_keys(KEY, B), 0.2, 0.4,
                           [9.999, 2.0, 4.0, 0.0], [0.2, 0.0, 1.0, 0.5], B),
     _direct_ge),
    ("poisson", lambda: S.poisson_arrivals(S.split_keys(KEY, B), 2.0, B),
     _direct_poisson),
    ("poisson-per-instance",
     lambda: S.poisson_arrivals(S.split_keys(KEY, B),
                                [0.0, 0.2, 2.0, 9.999], B),
     _direct_poisson),
]


@pytest.mark.parametrize("make,direct", [c[1:] for c in STREAM_CASES],
                         ids=[c[0] for c in STREAM_CASES])
@pytest.mark.parametrize("chunk", [None, 40])
def test_knuth_streams_match_jax_poisson(make, direct, chunk):
    stream = make()
    assert stream.chunk_fn in (streams._ge_chunk_poisson_knuth,
                               streams._poisson_chunk_knuth)
    x, side = S.materialize_stream(stream, T, chunk_size=chunk)
    assert np.array_equal(np.asarray(x), np.asarray(direct(stream, side)))


def _trace_counts(stream):
    """Trace the stream's chunk over the fleet once; return the sampler
    counts the trace added."""
    before = dict(streams.POISSON_SAMPLER_TRACES)
    tids = jnp.arange(8, dtype=jnp.int32)
    jax.make_jaxpr(jax.vmap(
        lambda p: stream.chunk_fn(p, stream.init_fn(p), tids)))(stream.params)
    return {k: streams.POISSON_SAMPLER_TRACES[k] - before.get(k, 0)
            for k in ("knuth", "general")}


KNUTH_CASES = [
    ("ge-poisson", lambda: S.ge_arrivals(KEY, 0.3, 0.2, 2.0, 0.2, B),
     streams._ge_chunk_poisson_knuth),
    ("poisson", lambda: S.poisson_arrivals(KEY, 8.0, B),
     streams._poisson_chunk_knuth),
]


@pytest.mark.parametrize("make,chunk_fn", [c[1:] for c in KNUTH_CASES],
                         ids=[c[0] for c in KNUTH_CASES])
def test_low_rates_take_knuth_path(make, chunk_fn):
    a, b = make(), make()
    # the same function object across constructions: the identity-keyed
    # compile caches hit
    assert a.chunk_fn is chunk_fn and b.chunk_fn is chunk_fn
    assert _trace_counts(a) == {"knuth": 1, "general": 0}


GENERAL_CASES = [
    ("bursty", lambda: S.bursty_arrivals(KEY, B), 1),
    ("bursty-diurnal", lambda: S.bursty_arrivals(KEY, B, diurnal_period=16),
     2),
    ("poisson-12", lambda: S.poisson_arrivals(KEY, 12.0, B), 1),
    ("poisson-one-at-10",
     lambda: S.poisson_arrivals(KEY, [2.0, 0.2, 10.0, 1.0], B), 1),
    ("ge-high-rate", lambda: S.ge_arrivals(KEY, 0.3, 0.2, 20.0, 2.0, B), 1),
]


@pytest.mark.parametrize("make,n_draws", [c[1:] for c in GENERAL_CASES],
                         ids=[c[0] for c in GENERAL_CASES])
def test_other_rates_take_general_path(make, n_draws):
    stream = make()
    assert stream.chunk_fn not in (streams._ge_chunk_poisson_knuth,
                                   streams._poisson_chunk_knuth)
    assert _trace_counts(stream) == {"knuth": 0, "general": n_draws}


def test_ge_built_under_jit_takes_general_path():
    """Traced rates are not known at construction: the general sampler,
    which draws the same counts as the Knuth-only stream."""
    seen = {}

    @jax.jit
    def draw(rate_h):
        stream = S.ge_arrivals(KEY, 0.3, 0.2, rate_h, 0.2, B)
        seen["chunk_fn"] = stream.chunk_fn
        return S.materialize_stream(stream, T)[0]

    before = streams.POISSON_SAMPLER_TRACES["general"]
    x = draw(2.0)
    assert seen["chunk_fn"] is streams._ge_chunk_poisson
    assert streams.POISSON_SAMPLER_TRACES["general"] > before
    knuth, _ = S.materialize_stream(
        S.ge_arrivals(KEY, 0.3, 0.2, 2.0, 0.2, B), T)
    assert np.array_equal(np.asarray(x), np.asarray(knuth))


# our scope is a path component of its own; jax.random.poisson's Knuth
# sampler shows up as ``jit(_poisson_knuth)``
_KNUTH_SCOPE = re.compile(r"[/(]poisson_knuth[)/]")


@pytest.mark.parametrize("rate_h,knuth", [(2.0, True), (20.0, False)],
                         ids=["knuth", "general"])
def test_compiled_program_names_its_sampler(rate_h, knuth):
    stream = S.combine(S.ge_arrivals(KEY, 0.3, 0.2, rate_h, 0.2, B),
                       S.constant_rents(0.5, B))
    gen = _compiled_gen(stream.init_fn, stream.chunk_fn, 2, 64, False)
    text = gen.lower(stream.params).compile().as_text()
    assert bool(_KNUTH_SCOPE.search(text)) == knuth
    assert ("_poisson_rejection" in text) == (not knuth)
