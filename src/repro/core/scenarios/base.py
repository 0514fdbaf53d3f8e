"""Scenario abstraction: composable on-device workload generators.

A *scenario* is to observations what ``PolicyFns`` is to policies — a pure
``(init_fn, chunk_fn)`` pair over a pytree of array params:

    gen_state0          = init_fn(params)
    gen_state', slab    = chunk_fn(params, gen_state, tids)

where ``tids`` is the ``[chunk]`` int32 vector of *global* slot indices this
call must emit and ``slab`` is an ``ObsSlab`` of per-slot observations
(arrivals ``x``, rents ``c``, optional realized Model-2 service costs
``svc`` and an int32 ``side`` channel such as the Gilbert-Elliot regime).
Params follow the policy convention: per-instance shapes describe one
instance; stack a leading ``[B]`` axis on every leaf and the same pair vmaps
over the fleet (``core.fleet.run_fleet(..., scenario=...)`` fuses generation
into the chunked scan, so device memory stays O(B * chunk) and no
observation array ever crosses the host->device boundary).

Counter-based keys — THE invariant
----------------------------------
Every random stream derives its slot-t randomness from
``jax.random.fold_in(key, t)`` (a counter-based construction), never from a
position inside a bulk ``(T,)`` draw.  Recursive state (the GE chain, ARMA
histories) rides in ``gen_state`` across chunk boundaries, but the
*innovations* feeding the recursion are counter-based.  Consequently a
stream's output is a pure function of ``(params, t)`` given the carried
state, and is **invariant to the chunk decomposition**: materializing the
whole horizon in one chunk, in 64-slot chunks, or generating slabs inside
the fleet scan all produce bit-identical observations.  That is what makes
``run_fleet(scenario=...)`` == materialize-then-run exact rather than
merely statistical (tests/test_scenarios.py).  The full set of key-folding
and bit-identity rules lives in ``docs/CONVENTIONS.md``; the engine layer
map in ``docs/ARCHITECTURE.md``.

Channel conventions
-------------------
* arrival streams emit ``(x [chunk] int32, side [chunk] int32)`` — ``side``
  is zeros when the process has no hidden state;
* rent streams emit ``c [chunk]`` in ``costs.default_float_dtype()``;
* service streams emit ``svc [chunk, K]`` and receive the slab's arrivals
  (``chunk_fn(params, state, tids, x)``) so Model-2 draws couple to the
  arrival process exactly like ``simulator.model2_service_matrix``.

``combinators.combine`` fuses one stream per channel into a ``Scenario``;
``mixture`` / ``regime_switch`` / ``antithetic_pairing`` / ``trace_*``
compose streams without touching the engine.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class ObsSlab(NamedTuple):
    """One ``[chunk]``-shaped window of generated observations (per
    instance; the engine vmaps a leading [B] axis on top)."""

    x: jnp.ndarray                     # [chunk] int32 arrivals
    c: jnp.ndarray                     # [chunk] float rents
    svc: Optional[jnp.ndarray] = None  # [chunk, K] realized service costs
    side: Optional[jnp.ndarray] = None # [chunk] int32 side channel


class Stream(NamedTuple):
    """One generated channel (arrivals, rents, or service costs).

    ``chunk_fn(params, state, tids) -> (state', values)`` where ``values``
    is the channel's per-slot payload (see module docstring).  Service
    streams take an extra ``x`` argument.  ``params`` leaves all carry a
    leading [B] axis (constructors broadcast); ``kind`` is one of
    ``"arrivals" | "rents" | "svc"`` and is checked by the combinators.
    ``has_side`` marks arrival streams whose side channel carries real
    information (the GE chain state; zeros otherwise) — materialization
    drops the channel when it doesn't.
    """

    name: str
    kind: str
    init_fn: Callable[[Any], Any]
    chunk_fn: Callable[..., Any]
    params: Any
    has_side: bool = False


class Scenario(NamedTuple):
    """A full workload generator: ``chunk_fn(params, gen_state, tids) ->
    (gen_state', ObsSlab)``.  ``has_svc`` declares whether slabs carry a
    realized service matrix (the engine falls back to Model-1 ``g * x``
    otherwise)."""

    name: str
    init_fn: Callable[[Any], Any]
    chunk_fn: Callable[[Any, Any, jnp.ndarray], Any]
    params: Any
    has_svc: bool = False
    has_side: bool = False

    @property
    def B(self) -> int:
        return jax.tree_util.tree_leaves(self.params)[0].shape[0]


# ----------------------------------------------------------------------
# Param/key plumbing shared by every stream constructor.
# ----------------------------------------------------------------------

def bcast(v, B: int, dtype=None) -> jnp.ndarray:
    """Broadcast a scalar / [B] value to a [B] param leaf."""
    a = jnp.asarray(v, dtype)
    return jnp.broadcast_to(a, (B,) + a.shape[1:] if a.ndim > 1 else (B,))


def split_keys(key, B: int) -> jnp.ndarray:
    """[B, 2] *independent* per-instance keys from one base key."""
    return jax.random.split(jnp.asarray(key), B)


def shared_keys(key, B: int) -> jnp.ndarray:
    """[B, 2] copies of ONE key: every instance replays the same sample
    path (the sweep-figure idiom — one trace scored at many grid points)."""
    return jnp.broadcast_to(jnp.asarray(key)[None, :], (B, 2))


def as_keys(key, B: int) -> jnp.ndarray:
    """Accept a single key (-> independent splits) or an explicit [B, 2]
    key array (returned as-is)."""
    key = jnp.asarray(key)
    if key.ndim == 1:
        return split_keys(key, B)
    if key.shape[0] != B:
        raise ValueError(f"key batch {key.shape[0]} != B={B}")
    return key


def slot_keys(key, tids: jnp.ndarray) -> jnp.ndarray:
    """[chunk, 2] counter-based per-slot keys: ``fold_in(key, t)``."""
    return jax.vmap(lambda t: jax.random.fold_in(key, t))(tids)


def slot_uniform(key, tids: jnp.ndarray, salt: Optional[int] = None,
                 dtype=jnp.float32) -> jnp.ndarray:
    """[chunk] independent U(0,1) draws, one per global slot index.

    THE counter-keyed uniform primitive every hot stream draws through
    (``bernoulli_arrivals``, ``uniform_rents`` / ``na_rents``, the GE chain
    and its bernoulli emissions): slot ``t`` draws from
    ``fold_in(fold_in(key, t), salt)`` (the salt fold only when given), so
    the draws at any split of the counters are the draws of the whole.
    """
    ks = slot_keys(key, tids)
    if salt is not None:
        ks = jax.vmap(lambda k: jax.random.fold_in(k, salt))(ks)
    return jax.vmap(lambda k: jax.random.uniform(k, (), dtype))(ks)


# ----------------------------------------------------------------------
# Materialization: run the same chunk_fn outside the simulator.
# ----------------------------------------------------------------------

def chunk_geometry(T: int, chunk_size: Optional[int]):
    """(n_chunks, padded T) for cutting a horizon into fixed chunks.  The
    ONE copy shared by ``materialize`` and the fleet engine — fused ==
    materialized bit-identity relies on both sides padding identically."""
    if chunk_size is None:
        return 1, T
    chunk = int(chunk_size)
    n = max(1, math.ceil(T / chunk))
    return n, n * chunk


@functools.lru_cache(maxsize=64)
def _compiled_gen(init_fn, chunk_fn, n_chunks: int, T_pad: int, extra_x: bool):
    """vmapped whole-horizon generator for one (init_fn, chunk_fn) pair."""
    chunk = T_pad // n_chunks

    def gen_one(params, *xs):
        state = init_fn(params)

        def run(state, t0):
            tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
            args = (params, state, tids)
            if extra_x:
                args += (jax.lax.dynamic_slice_in_dim(xs[0], t0, chunk),)
            return chunk_fn(*args)

        if n_chunks == 1:
            _, vals = run(state, jnp.asarray(0, jnp.int32))
            return vals
        t0s = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
        _, vals = jax.lax.scan(run, state, t0s)
        return jax.tree_util.tree_map(
            lambda a: a.reshape((T_pad,) + a.shape[2:]), vals)

    return jax.jit(jax.vmap(gen_one))


def materialize_stream(stream: Stream, T: int, chunk_size: Optional[int] = None,
                       x=None):
    """Run one stream over the whole horizon; returns its values pytree with
    leaves shaped ``[B, T, ...]``.  Chunk-invariant: any ``chunk_size``
    produces bit-identical values (the counter-key construction)."""
    n_chunks, T_pad = chunk_geometry(T, chunk_size)
    args = (stream.params,)
    if stream.kind == "svc":
        if x is None:
            raise ValueError("service streams need the arrival slab x")
        x = jnp.asarray(x, jnp.int32)
        if T_pad > T:
            x = jnp.pad(x, ((0, 0), (0, T_pad - T)))
        args += (x,)
    gen = _compiled_gen(stream.init_fn, stream.chunk_fn, n_chunks, T_pad,
                        stream.kind == "svc")
    vals = gen(*args)
    return jax.tree_util.tree_map(lambda a: a[:, :T], vals)


def materialize(scenario: Scenario, T: int, chunk_size: Optional[int] = None):
    """Materialize a scenario's observations: ``(x, c, svc, side)`` numpy
    arrays shaped [B, T] (svc [B, T, K]; svc/side None when absent).

    This is the reference the fused engine is proven against: for any
    ``chunk_size`` here and any chunk/stream configuration in ``run_fleet``,
    observations (and therefore simulation results) are bit-identical.
    """
    n_chunks, T_pad = chunk_geometry(T, chunk_size)
    gen = _compiled_gen(scenario.init_fn, scenario.chunk_fn, n_chunks, T_pad,
                        False)
    slab = gen(scenario.params)
    crop = lambda a: None if a is None else np.asarray(a[:, :T])
    # an all-zeros side channel (side-less arrival process) is the engine
    # default anyway — don't materialize dead [B, T] bytes for it
    side = crop(slab.side) if scenario.has_side else None
    return crop(slab.x), crop(slab.c), crop(slab.svc), side
