"""Multi-service hosting: N services sharing one edge node's storage.

The model (Online Service Caching and Routing at the Edge with Unknown
Arrivals, 2107.10446): each service keeps its own level set / g-curve /
fetch cost, the edge constrains the SUM of hosted fractions, and each
service sees its own arrival stream while the rent (spot price of the one
edge) is common.  This module maps that problem onto the existing fleet
engine along two complementary axes — no engine changes, both bitwise
N=1-identical to the single-service paths (tests/test_multi_service.py):

* **Per-service lanes** (online policies): service n of instance b is fleet
  row ``b * N + n`` of an ordinary [B*N] fleet (``ServiceFleet.lane_fleet``)
  driven by a ``tile_services``-salted scenario — every engine axis
  (chunking, streaming, meshes, ``n_seeds``, policy fan-out, the stepper)
  applies unchanged.  Independent lanes are capacity-OBLIVIOUS:
  ``capacity_overflow`` measures how far a lane schedule exceeds the shared
  capacity.
* **Joint states** (offline OPT): the feasible per-service level
  combinations of each instance become the states of a matrix-M
  ``HostingGrid`` (``costs.ServiceSet.joint_grid``), and ``joint_scenario``
  reduces the tiled per-service streams to one ``[B, chunk]`` joint slab
  (x summed, rent from lane 0, per-level service costs gathered per joint
  state).  ``offline_opt_services`` then runs the UNCHANGED fleet DP over
  the joint states — capacity-respecting by construction, proven against
  ``policies.offline_opt.brute_force_joint_opt``.

The N-to-joint reduction runs under the named scope ``joint_reduce``, and
``JOINT_SCENARIO_TRACES`` counts its traces by ``(N, J)``.

Engine-invariant documentation lives in docs/ARCHITECTURE.md and
docs/CONVENTIONS.md.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import (HostingGrid, ServiceSet, default_float_dtype,
                              joint_hosting_grid)
from repro.core.fleet import (FleetBatch, FleetOfflineResult, FleetResult,
                              evaluate_schedule_fleet, fleet_stepper,
                              offline_opt_fleet, run_fleet)
from repro.core.policies.alpha_rr import AlphaRR
from repro.core.policies.base import PolicyFns
from repro.core.scenarios.base import ObsSlab, Scenario
from repro.core.scenarios.combinators import tile_services

# test hook: trace-time counts of joint-scenario reductions, keyed by
# (services N, joint states J)
JOINT_SCENARIO_TRACES = collections.Counter()


@dataclasses.dataclass(frozen=True)
class ServiceFleet:
    """B multi-service instances (one ``ServiceSet`` each, a common N) with
    per-instance horizons — the container both mappings start from."""

    sets: Tuple[ServiceSet, ...]
    T: np.ndarray                      # [B] per-instance horizons
    # the joint-state tables, built on first use and kept: the sets are
    # immutable, and at fleet width a build takes seconds on the host
    _joint: dict = dataclasses.field(default_factory=dict, init=False,
                                     repr=False, compare=False)

    def __post_init__(self):
        object.__setattr__(self, "sets", tuple(self.sets))
        if not self.sets:
            raise ValueError("need at least one instance")
        Ns = {ss.N for ss in self.sets}
        if len(Ns) != 1:
            raise ValueError(f"instances must share one service count, "
                             f"got N in {sorted(Ns)}")
        object.__setattr__(
            self, "T",
            np.broadcast_to(np.asarray(self.T, np.int32),
                            (len(self.sets),)).copy())

    @property
    def B(self) -> int:
        return len(self.sets)

    @property
    def N(self) -> int:
        return self.sets[0].N

    def lane_grid(self) -> HostingGrid:
        """[B*N] single-service grid: service n of instance b is row
        ``b * N + n`` (instance-major, service-minor — the ``tile_services``
        row order)."""
        return HostingGrid.from_costs(
            [cc for ss in self.sets for cc in ss.services])

    def lane_fleet(self) -> FleetBatch:
        """The obs-less [B*N] lane fleet (pair with a tiled scenario)."""
        return FleetBatch.for_scenario(self.lane_grid(),
                                       np.repeat(self.T, self.N))

    def joint_grid(self) -> HostingGrid:
        """[B] matrix-M joint-state grid (mixed state counts padded), built
        once per fleet."""
        if "grid" not in self._joint:
            self._joint["grid"] = joint_hosting_grid(list(self.sets))
        return self._joint["grid"]

    def joint_tables(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(idx, g_lane)``, each [B, N, J]: every joint state's level index
        (int32) and service cost (float32) per service, built once per
        fleet.  Padded joint states of mixed-J fleets repeat their set's
        last real state."""
        if "tables" not in self._joint:
            B, N = self.B, self.N
            J = max(ss.J for ss in self.sets)
            idx = np.zeros((B, N, J), np.int32)
            g_lane = np.zeros((B, N, J), np.float32)
            for b, ss in enumerate(self.sets):
                st = ss.joint_states()                  # [J_b, N]
                Jb = st.shape[0]
                idx[b, :, :Jb] = st.T
                idx[b, :, Jb:] = idx[b, :, Jb - 1:Jb]
                for n, cc in enumerate(ss.services):
                    g_lane[b, n, :Jb] = np.asarray(cc.g,
                                                   np.float32)[st[:, n]]
                    g_lane[b, n, Jb:] = g_lane[b, n, Jb - 1]
            self._joint["tables"] = (idx, g_lane)
        return self._joint["tables"]

    def joint_fleet(self) -> FleetBatch:
        """The obs-less [B] joint fleet (pair with ``joint_scenario``)."""
        return FleetBatch.for_scenario(self.joint_grid(), self.T)


def service_fleet(sets: Sequence[ServiceSet], T) -> ServiceFleet:
    """Construct a ``ServiceFleet`` (``T`` scalar or [B])."""
    return ServiceFleet(sets=tuple(sets), T=T)


def service_scenario(sfleet: ServiceFleet, scenario: Scenario) -> Scenario:
    """The [B*N] per-service form of ``scenario``: a [B]-row scenario is
    ``tile_services``-expanded (per-service key salting, shared rent); an
    already-[B*N]-row scenario passes through untouched."""
    B_sc = scenario.B
    if B_sc == sfleet.B * sfleet.N:
        return scenario
    if B_sc != sfleet.B:
        raise ValueError(f"scenario B={B_sc} matches neither B={sfleet.B} "
                         f"nor B*N={sfleet.B * sfleet.N}")
    return tile_services(scenario, sfleet.N)


def alpha_rr_per_service(sfleet: ServiceFleet) -> PolicyFns:
    """alpha-RR run independently per service: the plain ``AlphaRR`` policy
    batch on the lane fleet — each lane is bitwise a standalone
    single-service alpha-RR run (capacity-oblivious; see
    ``capacity_overflow``)."""
    return AlphaRR.fleet(sfleet.lane_fleet())


# ----------------------------------------------------------------------
# Per-service lane runs (online policies).
# ----------------------------------------------------------------------

@dataclasses.dataclass
class ServiceFleetResult:
    """A lane-fleet ``FleetResult`` plus the [B, N] row bookkeeping."""

    fleet: FleetResult
    B: int
    N: int

    def service_view(self, a) -> np.ndarray:
        """Reshape a lane-row-leading array to ``[P, B, N, S, ...]``
        (policy-major, instance, service, seed-minor — the engine's row
        layout with rows ``((p * B + b) * N + n) * S + s``)."""
        a = np.asarray(a)
        S = self.fleet.n_seeds
        P = self.fleet.n_policies
        return a.reshape((P, self.B, self.N, S) + a.shape[1:])

    @property
    def total(self) -> np.ndarray:
        return self.service_view(self.fleet.total)

    @property
    def edge_total(self) -> np.ndarray:
        """[P, B, S] cost of the whole edge (summed over services)."""
        return self.total.sum(axis=2)


def run_fleet_services(policy, sfleet: ServiceFleet, *,
                       scenario: Scenario, **kwargs) -> ServiceFleetResult:
    """``run_fleet`` over the per-service lanes: ``policy`` (or a fan-out
    list) is built against ``sfleet.lane_fleet()`` (e.g.
    ``alpha_rr_per_service``); ``scenario`` is [B]-row (auto-tiled) or
    already [B*N].  Every ``run_fleet`` keyword (chunking, ``stream=``,
    ``n_seeds=``, backends, meshes) passes straight through — at N=1 the
    call IS the single-service ``run_fleet`` call, bit for bit."""
    res = run_fleet(policy, sfleet.lane_fleet(),
                    scenario=service_scenario(sfleet, scenario), **kwargs)
    return ServiceFleetResult(fleet=res, B=sfleet.B, N=sfleet.N)


def fleet_stepper_services(policy, sfleet: ServiceFleet, *,
                           scenario: Optional[Scenario] = None, **kwargs):
    """``fleet_stepper`` over the per-service lanes (rows ``b * N + n``;
    readbacks are lane-row-shaped — reshape with
    ``ServiceFleetResult.service_view`` semantics)."""
    if scenario is not None:
        scenario = service_scenario(sfleet, scenario)
    return fleet_stepper(policy, sfleet.lane_fleet(), scenario=scenario,
                         **kwargs)


def evaluate_schedule_services(sfleet: ServiceFleet, r_hist, *,
                               scenario: Optional[Scenario] = None,
                               **kwargs) -> ServiceFleetResult:
    """Price per-service schedules (``r_hist`` [B, N, T] or [B*N, T]) on
    the lane fleet — ``evaluate_schedule_fleet`` with the same tiled
    observations the lanes ran on."""
    r = np.asarray(r_hist)
    if r.ndim == 3:
        r = r.reshape(sfleet.B * sfleet.N, r.shape[-1])
    if scenario is not None:
        scenario = service_scenario(sfleet, scenario)
    res = evaluate_schedule_fleet(sfleet.lane_fleet(), r, scenario=scenario,
                                  **kwargs)
    return ServiceFleetResult(fleet=res, B=sfleet.B, N=sfleet.N)


def hosted_fractions(sfleet: ServiceFleet, r_hist) -> np.ndarray:
    """[B, N, T] hosted fractions of lane schedules (``r_hist`` [B*N, T]
    or [B, N, T] level indices)."""
    r = np.asarray(r_hist, np.int64)
    if r.ndim == 3:
        r = r.reshape(sfleet.B * sfleet.N, r.shape[-1])
    if r.shape[0] != sfleet.B * sfleet.N:
        raise ValueError(f"r_hist has {r.shape[0]} rows, expected "
                         f"B*N={sfleet.B * sfleet.N} (peel seed/policy axes "
                         "first)")
    lv = np.asarray(sfleet.lane_grid().levels)
    fr = np.take_along_axis(lv, r, axis=1)
    return fr.reshape(sfleet.B, sfleet.N, -1)


def capacity_overflow(sfleet: ServiceFleet, r_hist) -> np.ndarray:
    """[B, T] ``max(0, sum_n hosted fraction - capacity)`` per slot — the
    shared-capacity violation of independent per-service schedules (the
    joint DP's schedules are 0 everywhere by construction)."""
    tot = hosted_fractions(sfleet, r_hist).sum(axis=1)
    cap = np.asarray([ss.cap for ss in sfleet.sets])[:, None]
    return np.maximum(tot - cap, 0.0)


# ----------------------------------------------------------------------
# Joint-state runs (capacity-respecting offline OPT).
# ----------------------------------------------------------------------

def _reshape_sub(params, B: int, N: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.reshape(jnp.asarray(a),
                              (B, N) + jnp.shape(jnp.asarray(a))[1:]),
        params)


@functools.lru_cache(maxsize=32)
def _joint_fns(sub_init, sub_chunk, has_svc: bool, has_side: bool):
    """(init_fn, chunk_fn) of a joint-state scenario, memoized on the tiled
    scenario's *functions* (the ``_combine_fns`` convention, so the
    identity-keyed compile caches downstream hit across constructions).

    The wrapper vmaps the tiled per-service generator over its [N] axis and
    reduces the N sub-slabs to ONE joint slab: ``x`` summed, ``c`` from
    service lane 0 (one edge, one rent stream — ``tile_services``' shared
    rent group makes all lanes identical anyway), and the per-joint-state
    service channel gathered per service and summed — Model-2 slabs via
    ``idx`` column gathers, Model-1 via the per-state ``g_lane`` prices.
    Both reductions are one-term identities at N=1, which is the bitwise
    N=1 anchor of the joint DP path."""

    def init_fn(params):
        return jax.vmap(sub_init)(params["sub"])

    def chunk_fn(params, state, tids):
        st2, slab = jax.vmap(lambda p, s: sub_chunk(p, s, tids))(
            params["sub"], state)
        x_sub = slab.x                                  # [N, chunk]
        idx = params["idx"]                             # [N, J] int32
        N, chunk = x_sub.shape
        J = idx.shape[-1]
        JOINT_SCENARIO_TRACES[(N, J)] += 1
        # a named scope labels the reduction's ops in a profiler trace
        with jax.named_scope("joint_reduce"):
            if has_svc:
                svc_sub = slab.svc                      # [N, chunk, K]
                gathered = jnp.take_along_axis(
                    svc_sub,
                    jnp.broadcast_to(idx[:, None, :], (N, chunk, J)),
                    axis=2)                             # [N, chunk, J]
                svc = jnp.sum(gathered, axis=0)
            else:
                g_lane = params["g_lane"]               # [N, J]
                svc = jnp.sum(x_sub[:, :, None].astype(g_lane.dtype)
                              * g_lane[:, None, :], axis=0)
            side = None if slab.side is None else slab.side[0]
            return st2, ObsSlab(x=jnp.sum(x_sub, axis=0), c=slab.c[0],
                                svc=svc, side=side)

    return init_fn, chunk_fn


def joint_scenario(sfleet: ServiceFleet, scenario: Scenario) -> Scenario:
    """Reduce a (possibly still untiled) per-service scenario to the [B]
    JOINT-state scenario that drives ``sfleet.joint_fleet()``: one slab per
    instance with per-joint-state service costs (see ``_joint_fns``).
    Padded joint states of mixed-J fleets gather their set's last real
    state — priced ``+inf`` by the grid mask, never selected."""
    tiled = service_scenario(sfleet, scenario)
    B, N = sfleet.B, sfleet.N
    idx, g_lane = sfleet.joint_tables()
    params = {"sub": _reshape_sub(tiled.params, B, N),
              "idx": jnp.asarray(idx),
              "g_lane": jnp.asarray(g_lane, default_float_dtype())}
    init_fn, chunk_fn = _joint_fns(tiled.init_fn, tiled.chunk_fn,
                                   tiled.has_svc, tiled.has_side)
    return Scenario(f"joint{N}({scenario.name})", init_fn, chunk_fn, params,
                    has_svc=True, has_side=tiled.has_side)


@dataclasses.dataclass
class ServiceOfflineResult:
    """Joint capacity-respecting OPT of a ``ServiceFleet``.

    ``joint`` is the raw fleet DP result on the joint-state grid
    (``joint.r_hist`` rows are JOINT-state indices); ``service_schedules``
    translates them back to per-service level indices."""

    joint: FleetOfflineResult
    sfleet: ServiceFleet

    @property
    def cost(self) -> np.ndarray:
        return self.joint.cost

    def service_schedules(self) -> np.ndarray:
        """[rows, N, T] per-service level-index schedules (rows are the
        DP result's rows: instance-major, seed-minor)."""
        st = np.asarray(self.joint.r_hist, np.int64)
        S = self.joint.n_seeds
        out = np.zeros((st.shape[0], self.sfleet.N, st.shape[1]), np.int64)
        for row in range(st.shape[0]):
            states = self.sfleet.sets[row // S].joint_states()
            out[row] = states[st[row]].T
        return out


def offline_opt_services(sfleet: ServiceFleet, *, scenario: Scenario,
                         **kwargs) -> ServiceOfflineResult:
    """The joint capacity-respecting OPT: the UNCHANGED fleet DP
    (``offline_opt_fleet`` — materialized or checkpointed, chunked or
    streamed) over the joint-state grid, driven by the
    joint scenario.  Every keyword passes through.  Feasibility is free:
    infeasible level combinations are simply not states."""
    res = offline_opt_fleet(sfleet.joint_fleet(),
                            scenario=joint_scenario(sfleet, scenario),
                            **kwargs)
    return ServiceOfflineResult(joint=res, sfleet=sfleet)


def offline_opt_per_service(sfleet: ServiceFleet, *, scenario: Scenario,
                            **kwargs) -> FleetOfflineResult:
    """The capacity-OBLIVIOUS per-service OPT: ``offline_opt_fleet`` on the
    independent lanes.  Summed over services it lower-bounds the joint
    optimum (relaxing the capacity constraint can only help), and equals it
    when capacity never binds — both directions are tested."""
    return offline_opt_fleet(sfleet.lane_fleet(),
                             scenario=service_scenario(sfleet, scenario),
                             **kwargs)
