"""Slotted hosting simulator: one scan per instance, one jit(vmap(scan)) per
*fleet*.

Conventions (paper §2.5/§2.6):
  * slots are 1..T; ``r_hist[t]`` is the level *held during* slot t
    (r_1 = 0 for all online policies);
  * per-slot cost = rent + service while holding, plus fetch
    ``M * (lv[r_{t+1}] - lv[r_t])^+`` paid when the policy upgrades for the
    next slot.  Online policies also pay for a final upgrade decided at slot
    T (they cannot know the horizon ended); offline policies never upgrade
    at T.  ``evaluate_schedule`` charges fetches on entry so both styles are
    scored identically.

Batched / fleet engine
----------------------
Policies are pure ``(init_fn, step_fn)`` pairs over a pytree of array
params (see ``policies/base.py``).  ``run_policy`` runs ONE instance;
``run_policy_batch`` takes a ``PolicyFns`` whose params carry a leading
[B] axis (built by the policies' ``.batch`` classmethods from a stacked
``costs.HostingGrid``) plus [B, T]-shaped observations, and runs all B
independent hosting problems as a single compiled ``jit(vmap(scan))``.
``core/fleet.py`` layers device sharding (``shard_map`` over the ``fleet``
mesh axis), mixed per-instance horizons, T-chunked streaming, and fused
on-device workload generation (``run_fleet(scenario=...)`` feeds
``sim_chunk_core`` slabs emitted by a ``core.scenarios.Scenario`` inside
the scan instead of slices of a resident obs array — bit-identical, with
O(B * chunk) device memory) on top.

The shared kernel is ``sim_chunk_core``: it scans a ``[t0, t0 + chunk)``
slot window carrying ``(policy state, accumulator)``, so chaining it over
chunks reduces in exactly the same sequential order as one long scan
(chunked == unchunked bit-for-bit), and its valid-slot mask freezes state /
adds exactly 0.0 past an instance's own horizon (mixed-T batches match
per-instance runs bit-for-bit).  The whole-horizon entry points here are
its one-chunk, full-T_len special case.

Mixed-K batches are padded to a common K with a validity ``mask`` (see
``HostingGrid``); padded levels cost ``+BIG``/``+inf`` so they are never
selected, which makes batched level indices mean exactly what they mean in
the unpadded per-instance run — ``run_policy_batch`` output matches
``run_policy`` bit-for-bit instance by instance (tests/test_batched_engine,
tests/test_fleet_engine).

All entry points finish with one *fused* device reduction: the [3] totals
vector (rent/service/fetch), the [K] level-occupancy histogram and the
trace leave the device in a single transfer instead of four ``jnp.sum``
round-trips plus a host-side ``np.bincount``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import HostingCosts, HostingGrid, default_float_dtype
from repro.core.policies.base import (OnlinePolicy, PolicyFns, SlotObs,
                                      freeze_invalid)


@dataclasses.dataclass
class SimResult:
    total: float
    fetch: float
    rent: float
    service: float
    r_hist: np.ndarray        # [T] int level indices
    level_slots: np.ndarray   # [K] #slots spent at each level (the histograms)
    route: float = 0.0        # routing-cost term (``route=`` runs only)

    @property
    def per_slot(self) -> float:
        return self.total / len(self.r_hist)


@dataclasses.dataclass
class BatchSimResult:
    """[B]-structured results of one batched simulation."""

    total: np.ndarray         # [B]
    fetch: np.ndarray         # [B]
    rent: np.ndarray          # [B]
    service: np.ndarray      # [B]
    r_hist: np.ndarray        # [B, T] int level indices
    level_slots: np.ndarray   # [B, K] slots spent at each level

    @property
    def B(self) -> int:
        return self.total.shape[0]

    @property
    def per_slot(self) -> np.ndarray:
        return self.total / self.r_hist.shape[1]

    def instance(self, i: int) -> SimResult:
        return SimResult(total=float(self.total[i]), fetch=float(self.fetch[i]),
                         rent=float(self.rent[i]), service=float(self.service[i]),
                         r_hist=self.r_hist[i], level_slots=self.level_slots[i])


def _obs_arrays(costs: HostingCosts, x, c, svc, side):
    dt = default_float_dtype()
    x = jnp.asarray(x, jnp.int32)
    c = jnp.asarray(c, dt)
    T = x.shape[0]
    if svc is None:
        gv = jnp.asarray(costs.g, dt)
        svc = x[:, None].astype(dt) * gv[None, :]
    else:
        svc = jnp.asarray(svc, dt)
    if side is None:
        side = jnp.zeros((T,), jnp.int32)
    return x, c, svc, side


# ----------------------------------------------------------------------
# Fused simulation core (shared by the single, the batched and the fleet
# entry points).
# ----------------------------------------------------------------------

def sim_acc0(K: int, dt, n_sums: int = 3) -> dict:
    """Zero accumulator for the in-carry reductions: [3] rent/service/fetch
    sums (plus a 4th routing slot when the chunk runs with ``route=``) and
    the [K] level-occupancy histogram."""
    return {"sums": jnp.zeros((n_sums,), dt),
            "counts": jnp.zeros((K,), jnp.int32)}


def _fetch_between(M, K, r_from, r_to, lv_from, lv_to):
    """Fetch cost of the transition ``r_from -> r_to``.

    Scalar ``M`` is the paper's rank-one form ``M * (lv_to - lv_from)^+``;
    a matrix ``M`` ([K, K] per instance, see ``HostingGrid``'s
    "Matrix-valued M") prices the transition explicitly — the joint
    multi-service grids of ``costs.ServiceSet``.  The branch is static
    (ndim at trace time), so scalar-M programs are op-for-op what they
    were before the matrix form existed."""
    if jnp.ndim(M) >= 2:
        sel = (jnp.arange(K) == r_from)[:, None] & \
              (jnp.arange(K) == r_to)[None, :]
        return jnp.sum(jnp.where(sel, M, 0.0))
    return M * jnp.maximum(lv_to - lv_from, 0.0)


def sim_chunk_core(step_fn, include_final_fetch: bool,
                   params, lv, M, T_len, t0, carry, x, c, svc, side,
                   route=None):
    """Scan slots ``[t0, t0 + chunk)`` of ONE instance, carrying
    ``(policy state, accumulator)`` across chunk boundaries.

    This is the fleet engine's unit of work (``core/fleet.py`` chains it over
    T-chunks and vmaps/shard_maps it over instances); the whole-horizon run
    is the one-chunk special case.  Two masking rules make mixed horizons and
    chunking exact:

      * **valid slots** — global slot index ``t < T_len`` (``T_len`` is this
        instance's own horizon).  Invalid (padded-tail) slots add exactly
        ``0.0`` to every accumulator and leave the policy state *frozen*, so
        a fleet instance stops evolving at its own T and padded tails are a
        bitwise no-op (float ``a + 0.0 == a`` for the finite, non-negative
        costs here).
      * **last slot** — ``t == T_len - 1``: the speculative final fetch is
        zeroed here when ``include_final_fetch=False`` (per-instance, so
        mixed-T batches charge each instance at its own horizon).

    ``route`` (optional) is a ``[chunk, K]`` per-level routing-cost slab
    (2107.10446's request-routing term: what the slot's requests cost to
    route given each hosting level); it accumulates as a 4th ``sums`` slot
    selected by the SAME one-hot as the service channel.  ``route=None``
    (the default everywhere in the fleet engine) leaves the scan inputs
    and the [3] cost vector literally as they were — bitwise no-op.
    Matrix-valued ``M`` prices fetches explicitly (``_fetch_between``).

    The running totals ride along in the scan carry — strictly sequential
    accumulation, so the vmapped batch reduces in exactly the same order as a
    single run, and a chunked run in exactly the same order as an unchunked
    one (a post-hoc ``jnp.sum`` is not: XLA picks a different reduction tree
    for [B, T] than for [T]).

    Returns ``(carry', r_hist [chunk])``.
    """
    K = lv.shape[-1]
    chunk = x.shape[-1]
    tids = t0 + jnp.arange(chunk, dtype=jnp.int32)

    def step(carry, inp):
        state, acc = carry
        if route is None:
            t, x_t, c_t, svc_t, side_t = inp
        else:
            t, x_t, c_t, svc_t, side_t, route_t = inp
        valid_t = t < T_len
        last_t = t == T_len - 1
        r_t = state["r"]
        # one-hot selections instead of gathers/scatters: bit-identical, but
        # elementwise ops vectorise across the vmapped instance axis where
        # per-row dynamic indexing does not (see alpha_rr_step)
        onehot_t = jnp.arange(K) == r_t
        lv_t = jnp.sum(jnp.where(onehot_t, lv, 0.0))
        rent_t = c_t * lv_t
        svc_cost_t = jnp.sum(jnp.where(onehot_t, svc_t, 0.0))
        new_state = step_fn(params, state, SlotObs(x_t, c_t, svc_t, side_t))
        new_state = freeze_invalid(valid_t, new_state, state)
        r_next = new_state["r"]
        lv_next = jnp.sum(jnp.where(jnp.arange(K) == r_next, lv, 0.0))
        fetch_t = _fetch_between(M, K, r_t, r_next, lv_t, lv_next)
        if not include_final_fetch:
            fetch_t = jnp.where(last_t, 0.0, fetch_t)
        if route is None:
            vec = jnp.stack([rent_t, svc_cost_t, fetch_t])
        else:
            route_cost_t = jnp.sum(jnp.where(onehot_t, route_t, 0.0))
            vec = jnp.stack([rent_t, svc_cost_t, fetch_t, route_cost_t])
        acc = {
            "sums": acc["sums"] + jnp.where(valid_t, vec, 0.0),
            "counts": acc["counts"]
                      + jnp.where(valid_t, onehot_t.astype(jnp.int32), 0),
        }
        return (new_state, acc), r_t

    xs = (tids, x, c, svc, side)
    if route is not None:
        xs = xs + (route,)
    # a named scope labels the per-slot scan's ops in a profiler trace
    with jax.named_scope("policy_step"):
        return jax.lax.scan(step, carry, xs)


def sim_chunk_lanes(step_fns, include_final_fetch: bool,
                    lane_params, lane_lv, lane_M, T_len, t0, carries,
                    x, c, lane_svc, side):
    """Step P heterogeneous policy *lanes* over ONE shared ``[chunk]`` obs
    slab — the stacked-policy carry path of the fan-out axis.

    ``carries`` is a tuple of per-lane ``(state, acc)`` pytrees (states are
    heterogeneous — different policies, different K — so a tuple, never a
    stacked array).  Each lane's slabs differ only in the per-level service
    channel (``lane_svc[p]`` is [chunk, K_p]: Model-1 prices from the lane's
    own g, Model-2 gathers the lane's columns out of the shared slab); x, c
    and side are the single generated stream.  Every lane is literally one
    ``sim_chunk_core`` call — the same op chain, the same in-carry reduction
    order, the same ``freeze_invalid`` masking as its standalone run — so
    fan-out == standalone holds *by construction*, not by accident of
    compilation.

    Returns ``(carries', r_hists)`` — tuples of per-lane chunk results.
    """
    new_carries, r_hists = [], []
    for step_fn, params, lv, M, carry, svc in zip(
            step_fns, lane_params, lane_lv, lane_M, carries, lane_svc):
        carry, r = sim_chunk_core(step_fn, include_final_fetch, params, lv, M,
                                  T_len, t0, carry, x, c, svc, side)
        new_carries.append(carry)
        r_hists.append(r)
    return tuple(new_carries), tuple(r_hists)


def _sim_core(init_fn, step_fn, include_final_fetch: bool,
              params, lv, M, x, c, svc, side, route=None):
    """One instance, whole horizon: the one-chunk case of ``sim_chunk_core``.

    Returns (r_hist [T], sums [3] = rent/service/fetch ([4] with a routing
    slab), counts [K]).
    """
    K = lv.shape[-1]
    T = x.shape[-1]
    carry0 = (init_fn(params),
              sim_acc0(K, lv.dtype, 3 if route is None else 4))
    (_, acc), r_hist = sim_chunk_core(
        step_fn, include_final_fetch, params, lv, M,
        jnp.asarray(T, jnp.int32), jnp.asarray(0, jnp.int32), carry0,
        x, c, svc, side, route)
    return r_hist, acc["sums"], acc["counts"]


@functools.lru_cache(maxsize=64)
def _compiled_core(init_fn, step_fn, include_final_fetch: bool, batched: bool,
                   has_route: bool = False):
    # has_route only keys the cache: a route-carrying call re-traces with
    # the extra operand, so it must not share a wrapper with routing-free
    # callers (whose traced program stays exactly the pre-routing one)
    core = functools.partial(_sim_core, init_fn, step_fn, include_final_fetch)
    if batched:
        core = jax.vmap(core)
    return jax.jit(core)


def run_policy(policy: OnlinePolicy, costs: HostingCosts, x, c,
               svc=None, side=None, include_final_fetch: bool = True,
               route=None) -> SimResult:
    """Simulate an online policy over the whole horizon (one instance).

    ``route`` (optional [T, K]) adds the per-level routing-cost term to the
    accounting (``SimResult.route``); omitted, the program is bitwise the
    routing-free one."""
    x, c, svc, side = _obs_arrays(costs, x, c, svc, side)
    dt = default_float_dtype()
    lv = jnp.asarray(costs.levels, dt)
    M = jnp.asarray(costs.M, dt)
    fns = policy.fns()
    args = () if route is None else (jnp.asarray(route, dt),)
    if fns.params is not None:
        core = _compiled_core(fns.init_fn, fns.step_fn, include_final_fetch,
                              False, route is not None)
    else:
        # legacy policy subclass (bound init/step, no pure pair): fresh
        # closures can't key a compile cache — run the same core uncompiled.
        core = functools.partial(_sim_core, fns.init_fn, fns.step_fn,
                                 include_final_fetch)
    r_hist, sums, counts = core(fns.params, lv, M, x, c, svc, side, *args)
    r_np = np.asarray(r_hist)
    sums = np.asarray(sums)
    rent_s, svc_s, fetch_s = (float(v) for v in sums[:3])
    route_s = float(sums[3]) if route is not None else 0.0
    return SimResult(
        total=rent_s + svc_s + fetch_s + route_s,
        fetch=fetch_s, rent=rent_s, service=svc_s,
        r_hist=r_np,
        level_slots=np.asarray(counts).astype(np.int64),
        route=route_s,
    )


def _batch_obs(grid: HostingGrid, x, c, svc, side):
    """Broadcast observations to [B, T] / [B, T, K] stacked form."""
    dt = default_float_dtype()
    B = grid.B
    x = jnp.asarray(x, jnp.int32)
    if x.ndim == 1:
        x = jnp.broadcast_to(x[None, :], (B, x.shape[0]))
    T = x.shape[1]
    c = jnp.asarray(c, dt)
    if c.ndim == 1:
        c = jnp.broadcast_to(c[None, :], (B, T))
    if svc is None:
        svc = x[:, :, None].astype(dt) * grid.g.astype(dt)[:, None, :]
    else:
        svc = jnp.asarray(svc, dt)
        if svc.ndim == 2:
            svc = jnp.broadcast_to(svc[None, :, :], (B,) + svc.shape)
    if side is None:
        side = jnp.zeros((B, T), jnp.int32)
    else:
        side = jnp.asarray(side, jnp.int32)
        if side.ndim == 1:
            side = jnp.broadcast_to(side[None, :], (B, T))
    return x, c, svc, side


def run_policy_batch(policy: PolicyFns, grid: HostingGrid, x, c,
                     svc=None, side=None,
                     include_final_fetch: bool = True) -> BatchSimResult:
    """Simulate B independent hosting instances as one ``jit(vmap(scan))``.

    Args:
      policy: pure-function policy batch (``AlphaRR.batch(grid)``, ...);
        every params leaf carries a leading [B] axis.
      grid: the stacked instances the *accounting* runs on.  Must match the
        grid the policy batch was built from (for RR-style restrictions,
        pass the restricted grid, e.g. ``grid.restrict_to_endpoints()``).
      x: [T] or [B, T] arrivals ([T] broadcasts across the batch).
      c: [T] or [B, T] rent costs.
      svc: optional [B, T, K] (or [T, K]) realized service costs; None means
        Model 1 (``g * x``) on each instance's own g row.
      side: optional [T] or [B, T] side-channel.

    Returns a ``BatchSimResult`` with one fused device->host transfer for
    all totals and histograms.
    """
    x, c, svc, side = _batch_obs(grid, x, c, svc, side)
    dt = default_float_dtype()
    core = _compiled_core(policy.init_fn, policy.step_fn, include_final_fetch,
                          True)
    r_hist, sums, counts = core(policy.params, grid.levels.astype(dt),
                                grid.M.astype(dt), x, c, svc, side)
    # float64 accumulation to match the scalar path's host-side addition
    sums = np.asarray(sums).astype(np.float64)    # [B, 3]
    return BatchSimResult(
        total=sums.sum(axis=1),
        rent=sums[:, 0], service=sums[:, 1], fetch=sums[:, 2],
        r_hist=np.asarray(r_hist),
        level_slots=np.asarray(counts).astype(np.int64),
    )


# ----------------------------------------------------------------------
# Schedule evaluation (offline schedules are arrays, not policies).
# ----------------------------------------------------------------------

def schedule_chunk_core(lv, M, T_len, t0, carry, r, c, svc, route=None):
    """Chunk of schedule evaluation for ONE instance; ``carry`` is
    ``(prev level entering the chunk, accumulator)``.

    Same sequential in-scan accumulation and the same valid-slot masking as
    ``sim_chunk_core``, for the same reasons: batched / single / chunked /
    unchunked evaluations must all reduce in the same order, and slots past
    an instance's own ``T_len`` must be bitwise no-ops (the held level is
    frozen too, so a padded tail never charges a fetch).  ``route`` and
    matrix-valued ``M`` behave exactly as in ``sim_chunk_core``.
    """
    K = lv.shape[-1]
    chunk = r.shape[-1]
    tids = t0 + jnp.arange(chunk, dtype=jnp.int32)

    def step(carry, inp):
        prev_t, acc = carry
        if route is None:
            t, r_t, c_t, svc_t = inp
        else:
            t, r_t, c_t, svc_t, route_t = inp
        valid_t = t < T_len
        onehot_t = jnp.arange(K) == r_t
        lv_t = jnp.sum(jnp.where(onehot_t, lv, 0.0))
        lv_prev = jnp.sum(jnp.where(jnp.arange(K) == prev_t, lv, 0.0))
        fetch_t = _fetch_between(M, K, prev_t, r_t, lv_prev, lv_t)
        rent_t = c_t * lv_t
        svc_cost_t = jnp.sum(jnp.where(onehot_t, svc_t, 0.0))
        if route is None:
            vec = jnp.stack([rent_t, svc_cost_t, fetch_t])
        else:
            route_cost_t = jnp.sum(jnp.where(onehot_t, route_t, 0.0))
            vec = jnp.stack([rent_t, svc_cost_t, fetch_t, route_cost_t])
        acc = {
            "sums": acc["sums"] + jnp.where(valid_t, vec, 0.0),
            "counts": acc["counts"]
                      + jnp.where(valid_t, onehot_t.astype(jnp.int32), 0),
        }
        prev_next = jnp.where(valid_t, r_t, prev_t).astype(jnp.int32)
        return (prev_next, acc), None

    xs = (tids, r, c, svc)
    if route is not None:
        xs = xs + (route,)
    return jax.lax.scan(step, carry, xs)


def _schedule_core(lv, M, r, x, c, svc, route=None):
    K = lv.shape[-1]
    T = r.shape[-1]
    carry0 = (jnp.asarray(0, jnp.int32),
              sim_acc0(K, lv.dtype, 3 if route is None else 4))
    (_, acc), _ = schedule_chunk_core(
        lv, M, jnp.asarray(T, jnp.int32), jnp.asarray(0, jnp.int32), carry0,
        r, c, svc, route)
    return acc["sums"], acc["counts"]


_schedule_one = jax.jit(_schedule_core)
_schedule_vmapped = jax.jit(jax.vmap(_schedule_core))


def evaluate_schedule(costs: HostingCosts, r_hist, x, c, svc=None,
                      route=None) -> SimResult:
    """Cost of an arbitrary hosting schedule ``r_hist`` ([T] level indices,
    entered from r=0 before slot 1; fetches charged on entry to each slot).
    ``route`` (optional [T, K]) adds the routing-cost term."""
    x, c, svc, _ = _obs_arrays(costs, x, c, svc, None)
    dt = default_float_dtype()
    lv = jnp.asarray(costs.levels, dt)
    r = jnp.asarray(r_hist, jnp.int32)
    args = () if route is None else (jnp.asarray(route, dt),)
    sums, counts = _schedule_one(lv, jnp.asarray(costs.M, dt), r, x, c, svc,
                                 *args)
    sums = np.asarray(sums)
    rent_s, svc_s, fetch_s = (float(v) for v in sums[:3])
    route_s = float(sums[3]) if route is not None else 0.0
    return SimResult(
        total=rent_s + svc_s + fetch_s + route_s,
        fetch=fetch_s, rent=rent_s, service=svc_s,
        r_hist=np.asarray(r),
        level_slots=np.asarray(counts).astype(np.int64),
        route=route_s,
    )


def evaluate_schedule_batch(grid: HostingGrid, r_hist, x, c,
                            svc=None) -> BatchSimResult:
    """Batched ``evaluate_schedule``: ``r_hist`` is [B, T]."""
    x, c, svc, _ = _batch_obs(grid, x, c, svc, None)
    dt = default_float_dtype()
    r = jnp.asarray(r_hist, jnp.int32)
    sums, counts = _schedule_vmapped(grid.levels.astype(dt),
                                     grid.M.astype(dt), r, x, c, svc)
    sums = np.asarray(sums).astype(np.float64)
    return BatchSimResult(
        total=sums.sum(axis=1),
        rent=sums[:, 0], service=sums[:, 1], fetch=sums[:, 2],
        r_hist=np.asarray(r),
        level_slots=np.asarray(counts).astype(np.int64),
    )


def model2_service_matrix(key, costs: HostingCosts, x, max_per_slot: int | None = None):
    """Realized Model-2 service costs, coupled across levels (one uniform per
    request; forwarded at level k iff u < g[k]).  Returns [T, K]."""
    x = jnp.asarray(x, jnp.int32)
    T = int(x.shape[0])
    R = int(max_per_slot if max_per_slot is not None else max(int(jnp.max(x)), 1))
    u = jax.random.uniform(key, (T, R))
    gv = jnp.asarray(costs.g, default_float_dtype())
    live = jnp.arange(R)[None, :] < x[:, None]              # [T, R]
    fwd = u[:, :, None] < gv[None, None, :]                 # [T, R, K]
    return jnp.sum(jnp.where(live[:, :, None] & fwd, 1.0, 0.0), axis=1)  # [T, K]
