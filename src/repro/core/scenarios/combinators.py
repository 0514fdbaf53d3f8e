"""Scenario combinators: declare mixed workloads instead of hand-assembling
observation arrays.

* ``combine``             — one stream per channel -> a full ``Scenario``.
* ``mixture``             — per-instance mixture over [B]: instance b plays
                            component ``component[b]``'s stream.
* ``mixture_from_weights``— sample that assignment from mixture weights.
* ``regime_switch``       — time-based switching at fixed slot boundaries.
* ``antithetic_pairing``  — negatively-associated instance pairs: (2m, 2m+1)
                            share a key, the odd member flips its uniforms.
* ``trace_scenario``      — deterministic playback of recorded [B, T] obs.
* ``with_seed``           — fold one Monte-Carlo seed into every stream key
                            (before the per-slot counter fold).
* ``with_prng_backend``   — route a scenario's (or stream's) counter-keyed
                            uniforms through a kernel backend
                            (``base.PRNG_BACKENDS``); bit-identical by the
                            backend-dispatch invariant.
* ``replicate_seeds``     — the MC axis: S seed-replicas of a B-instance
                            scenario as one [B*S] scenario
                            (``antithetic=True`` pairs replicas (2m, 2m+1)
                            on flip-capable streams).
* ``tile_services``       — the per-service axis: N service-replicas of a
                            B-instance scenario as one [B*N] scenario,
                            keys salted per service except in ``shared``
                            channel groups (default: one rent stream per
                            instance across its services).

Composition happens at the *stream* level, so combinator outputs are
ordinary streams: mixtures of regime-switched antithetic pairs are
one-liners and everything still fuses into the fleet scan.  Selection uses
compute-all-then-select (``jnp.where``), the same trick the policies use
for one-hot levels: every component advances its state and draws every
slot, which keeps the combinators vmap/shard_map-transparent and makes the
selected rows *bitwise equal* to running the selected component alone
(tests/test_scenarios.py::test_mixture_selects_components).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scenarios.base import (ObsSlab, PRNG_BACKENDS, Scenario,
                                       Stream, prng_dispatch)
from repro.core.scenarios import streams as _streams


@functools.lru_cache(maxsize=64)
def _backend_fns(init_fn, chunk_fn, backend: str):
    """Backend-bracketed (init_fn, chunk_fn), memoized on the wrapped
    *functions* + backend so repeated with_prng_backend() constructions
    yield identical function objects (the identity-keyed compile caches —
    ``base._compiled_gen``, the fleet engine cores — then key correctly on
    the backend choice, like ``_combine_fns``)."""

    def init2(params):
        with prng_dispatch(backend):
            return init_fn(params)

    def chunk2(params, state, tids, *extra):
        with prng_dispatch(backend):
            return chunk_fn(params, state, tids, *extra)

    return init2, chunk2


def with_prng_backend(scenario, backend: str):
    """Route every ``slot_uniform`` draw of a Scenario (or a single Stream)
    through ``backend`` (see ``base.PRNG_BACKENDS``).  "xla" — the
    canonical reference — returns the input unchanged; any other backend
    wraps ``init_fn``/``chunk_fn`` so the dispatch is baked in at trace
    time.  Observations are **bit-identical** across backends (the
    backend-dispatch invariant); draws the kernel does not cover (poisson,
    normal, float64 uniforms) silently stay on the reference path."""
    if backend not in PRNG_BACKENDS:
        raise ValueError(f"prng backend must be one of {PRNG_BACKENDS}, "
                         f"got {backend!r}")
    if backend == "xla":
        return scenario
    init2, chunk2 = _backend_fns(scenario.init_fn, scenario.chunk_fn,
                                 backend)
    return scenario._replace(init_fn=init2, chunk_fn=chunk2,
                             name=f"{scenario.name}@{backend}")


@functools.lru_cache(maxsize=256)
def _combine_fns(arr_fns, rent_fns, svc_fns):
    """(init_fn, chunk_fn) for a channel combination, memoized on the
    component *functions* (not params): combining the same stream families
    twice yields identical function objects, so the identity-keyed compile
    caches downstream hit instead of re-tracing per Scenario construction."""
    arr_init, arr_chunk = arr_fns
    rent_init, rent_chunk = rent_fns

    def init_fn(params):
        st = {"arr": arr_init(params["arr"]),
              "rent": rent_init(params["rent"])}
        if svc_fns is not None:
            st["svc"] = svc_fns[0](params["svc"])
        return st

    def chunk_fn(params, state, tids):
        # named scopes label the two generators' ops in a profiler trace
        with jax.named_scope("gen_arrivals"):
            sa, (x, side) = arr_chunk(params["arr"], state["arr"], tids)
        with jax.named_scope("gen_rents"):
            sr, c = rent_chunk(params["rent"], state["rent"], tids)
        st = {"arr": sa, "rent": sr}
        svc_v = None
        if svc_fns is not None:
            st["svc"], svc_v = svc_fns[1](params["svc"], state["svc"],
                                          tids, x)
        return st, ObsSlab(x=x, c=c, svc=svc_v, side=side)

    return init_fn, chunk_fn


def combine(arrivals: Stream, rents: Stream, svc: Optional[Stream] = None,
            name: Optional[str] = None) -> Scenario:
    """Fuse per-channel streams into one Scenario."""
    for s, kind in ((arrivals, "arrivals"), (rents, "rents")):
        if s.kind != kind:
            raise ValueError(f"{s.name} is a {s.kind} stream, expected {kind}")
    if svc is not None and svc.kind != "svc":
        raise ValueError(f"{svc.name} is a {svc.kind} stream, expected svc")
    params = {"arr": arrivals.params, "rent": rents.params}
    if svc is not None:
        params["svc"] = svc.params
    init_fn, chunk_fn = _combine_fns(
        (arrivals.init_fn, arrivals.chunk_fn),
        (rents.init_fn, rents.chunk_fn),
        None if svc is None else (svc.init_fn, svc.chunk_fn))
    name = name or f"{arrivals.name}+{rents.name}" + \
        (f"+{svc.name}" if svc is not None else "")
    return Scenario(name, init_fn, chunk_fn, params,
                    has_svc=svc is not None, has_side=arrivals.has_side)


def _check_same_kind(components: Sequence[Stream]) -> str:
    kinds = {s.kind for s in components}
    if len(kinds) != 1:
        raise ValueError(f"cannot mix stream kinds {sorted(kinds)}")
    return kinds.pop()


@functools.lru_cache(maxsize=256)
def _select_fns(comp_fns, by_time: bool):
    """(init_fn, chunk_fn) for compute-all-then-select composition, memoized
    on the component *functions* so repeated mixture()/regime_switch()
    constructions reuse the same function objects (and therefore hit the
    identity-keyed compile caches downstream, like ``_combine_fns``).

    ``by_time=False`` selects per instance by ``params["component"]``;
    ``by_time=True`` selects per slot by ``params["bounds"]`` boundaries.
    """

    def init_fn(params):
        return tuple(f[0](p) for f, p in zip(comp_fns, params["subs"]))

    def chunk_fn(params, state, tids, *extra):
        states, values = [], []
        for f, p, st in zip(comp_fns, params["subs"], state):
            st2, v = f[1](p, st, tids, *extra)
            states.append(st2)
            values.append(v)
        if by_time:
            sel = jnp.sum(tids[:, None] >= params["bounds"][None, :],
                          axis=1)                                # [chunk]
        else:
            sel = params["component"]                            # scalar
        out = values[0]
        for i in range(1, len(values)):
            pick = sel == i
            out = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    pick.reshape(pick.shape + (1,) * (a.ndim - pick.ndim))
                    if by_time else pick, b, a),
                out, values[i])
        return tuple(states), out

    return init_fn, chunk_fn


def _component_B(components: Sequence[Stream]) -> int:
    return jax.tree_util.tree_leaves(components[0].params)[0].shape[0]


def mixture(components: Sequence[Stream], component) -> Stream:
    """Per-instance mixture: instance b emits component ``component[b]``'s
    stream (all components must be the same channel kind).  Every
    component's state advances on every instance; the winner is selected
    per instance, so row b is bitwise the winner's own output."""
    kind = _check_same_kind(components)
    comp = np.asarray(component, np.int32)
    if np.any((comp < 0) | (comp >= len(components))):
        raise ValueError(f"component indices must be in [0, "
                         f"{len(components)}), got {comp}")
    params = {"component": jnp.asarray(comp),
              "subs": tuple(s.params for s in components)}
    init_fn, chunk_fn = _select_fns(
        tuple((s.init_fn, s.chunk_fn) for s in components), False)
    name = "mix(" + ",".join(s.name for s in components) + ")"
    return Stream(name, kind, init_fn, chunk_fn, params,
                  has_side=any(s.has_side for s in components))


def mixture_from_weights(components: Sequence[Stream], weights, key,
                         B: int) -> Stream:
    """Mixture with the per-instance assignment sampled once from
    ``weights`` (the declarative form of "30% bursty, 70% Bernoulli")."""
    w = np.asarray(weights, np.float64)
    comp = jax.random.choice(jnp.asarray(key), len(components), (B,),
                             p=jnp.asarray(w / w.sum()))
    return mixture(components, comp)


def regime_switch(components: Sequence[Stream],
                  boundaries: Sequence[int]) -> Stream:
    """Time-based switching: slots ``[boundaries[i-1], boundaries[i])`` play
    component i (``boundaries`` are global slot indices, strictly
    increasing, one fewer than components).  Every component keeps
    advancing its own state through foreign regimes, so for counter-based
    (stateless) components each regime's slots are bitwise the component's
    own slots."""
    kind = _check_same_kind(components)
    if len(boundaries) != len(components) - 1:
        raise ValueError("need len(components) - 1 boundaries")
    bounds = np.asarray(boundaries, np.int32)
    if bounds.size and np.any(np.diff(bounds) <= 0):
        raise ValueError("boundaries must be strictly increasing")
    B = _component_B(components)
    # [B, n-1] params leaf (every leaf needs the instance axis for vmap)
    params = {"bounds": jnp.broadcast_to(jnp.asarray(bounds)[None],
                                         (B,) + bounds.shape),
              "subs": tuple(s.params for s in components)}
    init_fn, chunk_fn = _select_fns(
        tuple((s.init_fn, s.chunk_fn) for s in components), True)
    name = "switch(" + ",".join(s.name for s in components) + ")"
    return Stream(name, kind, init_fn, chunk_fn, params,
                  has_side=any(s.has_side for s in components))


def antithetic_pairing(stream: Stream) -> Stream:
    """Negatively-associated instance pairs: instances (2m, 2m+1) share
    instance 2m's key and the odd member flips every slot uniform
    ``u -> 1 - u``.  Requires a stream with ``key`` and ``flip`` params
    (``bernoulli_arrivals``, ``uniform_rents``); pair sums of uniforms are
    exactly ``lo + hi`` (variance-reduction law in the tests)."""
    if not (isinstance(stream.params, dict) and "flip" in stream.params
            and "key" in stream.params):
        raise ValueError(f"{stream.name} does not support antithetic "
                         "pairing (no flip/key params)")
    B = stream.params["flip"].shape[0]
    even = (np.arange(B) // 2) * 2
    params = dict(stream.params)
    params["key"] = jnp.asarray(stream.params["key"])[even]
    params["flip"] = jnp.asarray(np.arange(B) % 2 == 1)
    return Stream(f"antithetic({stream.name})", stream.kind, stream.init_fn,
                  stream.chunk_fn, params, has_side=stream.has_side)


# ----------------------------------------------------------------------
# Monte-Carlo seed replication (the fleet engine's ``n_seeds=`` axis).
# ----------------------------------------------------------------------

def _map_key_leaves(params, leaf_fn, key_fn, pair_fn=None):
    """Structurally walk a params pytree, applying ``key_fn`` to every
    ``"key"`` dict entry (the stream-constructor convention: counter-based
    PRNG keys live under that name on every random stream) and ``leaf_fn``
    to every other array leaf.  Dict-name-aware on purpose — ``tree_map``
    cannot tell a key leaf from a coefficient leaf.

    ``pair_fn(key, flip) -> (key', flip')``, when given, takes over dicts
    that carry BOTH ``"key"`` and ``"flip"`` — the flip-capable streams
    (``bernoulli_arrivals``, ``uniform_rents``) that antithetic seed
    replication pairs up; every other keyed dict still goes through
    ``key_fn``."""
    if isinstance(params, dict):
        if pair_fn is not None and "key" in params and "flip" in params:
            key2, flip2 = pair_fn(params["key"], params["flip"])
            return {k: (key2 if k == "key" else flip2 if k == "flip"
                        else _map_key_leaves(v, leaf_fn, key_fn, pair_fn))
                    for k, v in params.items()}
        return {k: (key_fn(v) if k == "key"
                    else _map_key_leaves(v, leaf_fn, key_fn, pair_fn))
                for k, v in params.items()}
    if isinstance(params, (tuple, list)):
        return type(params)(_map_key_leaves(v, leaf_fn, key_fn, pair_fn)
                            for v in params)
    return leaf_fn(params)


def _fold_stacked(k, seeds):
    """``fold_in`` over a stacked key leaf ``[R, ..., 2]`` with per-row
    seeds ``[R]``.  Rows may carry extra stacked axes between the row axis
    and the key words — e.g. the joint multi-service scenario's
    ``[B, N, 2]`` sub-stream keys — and the row's seed broadcasts over
    them.  For the ordinary ``[R, 2]`` leaf the reshape is a no-op and
    this IS the plain ``vmap(fold_in)`` (bitwise)."""
    k = jnp.asarray(k)
    flat = k.reshape((-1,) + k.shape[-1:])
    s = jnp.repeat(seeds, flat.shape[0] // seeds.shape[0])
    return jax.vmap(jax.random.fold_in)(flat, s).reshape(k.shape)


def _bcast_rows(flag, like):
    """Right-pad a per-row ``[R]`` flag with singleton axes to broadcast
    against a stacked ``[R, ...]`` leaf."""
    return flag.reshape((-1,) + (1,) * (jnp.ndim(like) - 1))


def with_seed(obj, seed: int):
    """Fold one Monte-Carlo seed into every stream key of a ``Scenario`` or
    ``Stream``: ``key -> fold_in(key, seed)``.

    The fold happens *before* any per-slot ``fold_in(key, t)`` (and before
    the init-salt draws), so the result is an ordinary, legal standalone
    scenario — exactly the replica ``replicate_seeds`` packs at rows
    ``(b, seed)``.  Keyless streams (traces, constants, adversarial baits)
    are untouched: deterministic channels do not vary with the seed.
    """
    def fold(k):
        k = jnp.asarray(k)
        return _fold_stacked(k, jnp.full((k.shape[0],), seed, jnp.int32))
    params = _map_key_leaves(obj.params, lambda a: a, fold)
    return obj._replace(params=params, name=f"seed{seed}({obj.name})")


def replicate_seeds(obj, n_seeds: int, antithetic: bool = False):
    """S seed-replicas of a B-instance ``Scenario`` (or ``Stream``) as one
    [B*S] scenario — the Monte-Carlo axis folded into the stream keys.

    Row ``b * S + s`` (instance-major, seed-minor) carries instance ``b``'s
    params with ``fold_in(key, s)`` applied to every stream key, so it is
    **bit-identical** to running instance ``b`` standalone under
    ``with_seed(obj, s)``: no obs materialization, no benchmark-side key
    plumbing, and every downstream engine guarantee (chunk invariance,
    mesh transparency) holds per replica because a replica *is* a legal
    standalone instance.  Non-key param leaves are replicated row-wise.

    ``antithetic=True`` (even S required) pairs consecutive replicas on
    *flip-capable* streams (those carrying a ``flip`` next to their
    ``key``, i.e. ``bernoulli_arrivals`` / ``uniform_rents``): replicas
    ``(b, 2m)`` and ``(b, 2m + 1)`` share the pair fold ``fold_in(key, m)``
    and the odd member flips every slot uniform ``u -> 1 - u`` — the
    ``antithetic_pairing`` trick moved onto the seed axis, so pair sums of
    uniforms are exactly ``lo + hi`` and seed-mean CIs tighten at the same
    S for monotone statistics.  Even replicas are bitwise
    ``with_seed(obj, m)``'s rows on those streams; streams WITHOUT a flip
    param (GE chains, ARMA rents, Poisson, traces) keep the plain
    independent per-replica fold — antithesis only ever replaces
    independent replicas where the flip trick is exact.
    """
    S = int(n_seeds)
    if S < 1:
        raise ValueError(f"n_seeds must be >= 1, got {n_seeds}")
    B = jax.tree_util.tree_leaves(obj.params)[0].shape[0]
    seeds = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)       # [B*S]
    rep = lambda a: jnp.repeat(jnp.asarray(a), S, axis=0)
    if not antithetic:
        params = _map_key_leaves(obj.params, rep,
                                 lambda k: _fold_stacked(rep(k), seeds))
        return obj._replace(params=params, name=f"mc{S}({obj.name})")
    if S % 2:
        raise ValueError(f"antithetic replication needs an even n_seeds, "
                         f"got {n_seeds}")
    odd = (seeds % 2).astype(bool)
    params = _map_key_leaves(
        obj.params, rep, lambda k: _fold_stacked(rep(k), seeds),
        pair_fn=lambda k, f: (_fold_stacked(rep(k), seeds // 2),
                              jnp.logical_xor(rep(f),
                                              _bcast_rows(odd, rep(f)))))
    return obj._replace(params=params, name=f"mc{S}a({obj.name})")


def tile_services(obj, n_services: int, shared: Sequence[str] = ("rent",)):
    """N service-replicas of a B-instance ``Scenario`` (or ``Stream``) as
    one [B*N] object — the per-service arrival axis of a multi-service
    fleet (``core.services``).

    Row ``b * N + n`` (instance-major, service-minor) carries instance
    ``b``'s params with ``fold_in(key, n)`` applied to every stream key —
    the same counter-key salting discipline as ``replicate_seeds``, so
    each service's stream is an independent draw yet fully deterministic
    and chunk-invariant.  Non-key leaves are replicated row-wise.

    ``shared`` names top-level param groups (the ``combine`` channel names
    ``"arr"`` / ``"rent"`` / ``"svc"``) whose keys are replicated WITHOUT
    the service fold: the default ``("rent",)`` gives all N services of an
    instance the identical rent stream — one edge, one spot price — while
    arrivals (and Model-2 service draws) vary per service.  Service n's
    rows are bitwise the rows of a standalone scenario built with the same
    folds, and ``n_services=1`` returns ``obj`` unchanged (the N=1
    bit-identity anchor).  The service fold composes *before* the engine's
    seed fold (``replicate_seeds`` runs inside ``run_fleet``), so MC rows
    are ``fold_in(fold_in(key, n), s)`` — service-major, seed-minor.
    """
    N = int(n_services)
    if N < 1:
        raise ValueError(f"n_services must be >= 1, got {n_services}")
    if N == 1:
        return obj
    B = jax.tree_util.tree_leaves(obj.params)[0].shape[0]
    svc_ids = jnp.tile(jnp.arange(N, dtype=jnp.int32), B)      # [B*N]
    rep = lambda a: jnp.repeat(jnp.asarray(a), N, axis=0)
    folded = lambda p: _map_key_leaves(
        p, rep, lambda k: _fold_stacked(rep(k), svc_ids))
    plain = lambda p: _map_key_leaves(p, rep, rep)
    if isinstance(obj.params, dict):
        params = {k: (plain(v) if k in shared else folded(v))
                  for k, v in obj.params.items()}
    else:
        params = folded(obj.params)
    return obj._replace(params=params, name=f"svc{N}({obj.name})")


def _trace_svc_chunk(params, state, tids, x):
    tr = params["trace"]
    return state, jnp.take(tr, jnp.minimum(tids, tr.shape[0] - 1), axis=0)


def _trace_svc_init(params):
    return ()


def trace_scenario(x, c, B: Optional[int] = None, svc=None,
                   side=None) -> Scenario:
    """Deterministic playback of recorded observations through the fused
    engine (g-curve pipelines, real traces).  ``svc`` rides as a [B, T, K]
    trace when given."""
    arr = _streams.trace_arrivals(x, B=B, side=side)
    B_eff = arr.params["trace"].shape[0]
    rent = _streams.trace_rents(c, B=B_eff)
    svc_stream = None
    if svc is not None:
        svc_arr = jnp.asarray(svc)
        if svc_arr.ndim == 2:
            svc_arr = jnp.broadcast_to(svc_arr[None], (B_eff,) + svc_arr.shape)
        svc_stream = Stream("trace", "svc", _trace_svc_init, _trace_svc_chunk,
                            {"trace": svc_arr})
    return combine(arr, rent, svc=svc_stream, name="trace")
