"""Fleet engine: device-sharded, mixed-horizon, time-chunked simulation.

This is the layer above ``simulator.run_policy_batch``: it runs a *fleet* —
B independent hosting instances with possibly different horizons T_i — as
one compiled program sharded over a 1-D device mesh, optionally streaming
the time axis in fixed-size chunks.  (Engine-wide layer map:
``docs/ARCHITECTURE.md``; the invariants new code must preserve:
``docs/CONVENTIONS.md``.)  Three orthogonal mechanisms, each a
bitwise no-op when unused:

**[B] sharding** — the instance axis is embarrassingly parallel, so the
vmapped per-instance core is wrapped in ``shard_map`` over the ``fleet``
mesh axis (``sharding.specs.fleet_mesh``).  B is padded up to a device
multiple with dummy instances (``T = 0``: every slot invalid, zero cost,
frozen state) and results are sliced back, so sharded output ==
``run_policy_batch`` output bit-for-bit on any device count.

**Mixed horizons** — a ``FleetBatch`` stacks per-instance horizons ``T``
next to [B, T_max]-padded observations.  ``simulator.sim_chunk_core``
freezes policy state and adds exactly ``0.0`` to every accumulator on slots
at or past an instance's own T (see ``policies.base.freeze_invalid``), so
each instance's totals/trace match a standalone run at its own horizon, and
the final speculative fetch is charged at each instance's own last slot.

**Time streaming** — the horizon is cut into fixed-size chunks with the
``(policy state, accumulator)`` carry threaded across chunk boundaries;
accumulation order is unchanged, so chunked == unchunked bit-for-bit.  Two
drivers share the same chunk kernel:

  * ``chunk_size=...`` — an outer ``lax.scan`` over chunks on device (one
    XLA program; obs stay resident);
  * ``stream=True``    — a host loop feeding one [B, chunk] slab at a time
    to a jitted sharded chunk-step, so a T=10^6 trace never materialises
    [B, T_max] on device (device memory is O(B * chunk)).

``offline_opt_fleet`` applies the same three mechanisms to the offline DP
(forward recursion chunked and frozen past T_i with identity backpointers;
padded K levels priced ``+inf`` as in ``offline_opt_batch``), and adds a
fourth of its own:

**Checkpointed backtracking** — ``checkpointed=True`` replaces the
materialized [B, T, K] backpointer table with a two-pass recursion: the
forward value pass stores one [B, K] frontier checkpoint per chunk (plus
the generator state for scenario-fused runs), and the backtrack pass
replays the chunks in reverse, recomputing each chunk's argmin table on
the fly from its checkpoint — the same counter-keyed regeneration the
fused simulator relies on.  Bit-identical to the materialized path
wherever both fit (the recomputed tables come from the identical
``offline_opt.dp_fwd_chunk`` at the identical frontier), with device
memory O(B * chunk * K): exact OPT now reaches the same T = 10^6-10^7
horizons as ``run_fleet(collect_trace=False)``.  ``stream=True`` drives
both passes from the host one slab at a time; ``collect_schedule=False``
skips the backtrack for cost-only pricing with no O(T) output at all;
``offline_dp_memory_stats`` exposes the XLA-reported memory of the
compiled core for either path (the regression-gated
``kernel_bench.offline_dp_streaming`` row asserts the ratio).

**Scenario fusion** — every entry point alternatively accepts
``scenario=...`` (a ``core.scenarios.Scenario``) in place of materialized
observations: the generator's ``chunk_fn`` runs *inside* the chunked scan,
emitting one [B, chunk] slab of arrivals/rents (plus optional Model-2
service draws and side-state) per chunk, with the generator state threaded
through the scan carry next to the policy state.  Device memory stays
O(B * chunk) at any horizon and **zero** observation bytes cross the
host->device boundary (the streaming driver ships only a scalar chunk
offset).  Because scenario streams are counter-keyed (see
``core/scenarios/base.py``), fused generation is bit-identical to
materializing the same scenario (``scenarios.materialize`` /
``FleetBatch.from_scenario``) and running the classic path — for every
policy, the offline DP, and schedule evaluation, under every
mesh x chunking x driver configuration (tests/test_scenarios.py).  Pass
``collect_trace=False`` to drop the [B, T] ``r_hist`` output, the one
remaining O(T) device buffer, for T >= 10^6 fleets.

**Monte-Carlo seed axis** — every scenario-driven entry point accepts
``n_seeds=S``: the engine replicates the fleet to [B*S] rows
(instance-major, seed-minor) with seed ``s`` folded into every stream key
via ``scenarios.combinators.replicate_seeds`` — ``fold_in(key, s)``
*before* the per-slot ``fold_in(key, t)`` — so replica row ``(b, s)`` is
bit-identical to running instance ``b`` standalone under
``with_seed(scenario, s)``.  Replication, padding to the device multiple
and result unflattening all happen inside (composing with
shard_map/chunking/streaming); results carry ``n_seeds`` and a
``seed_view`` reshaping any [B*S]-leading array to [B, S], and
``mc_summary`` collapses the seed axis into per-instance means and
Student-t 95% CI half-widths (tests/test_mc_driver.py).  ``antithetic=True``
pairs the replicas (2m, 2m+1) on flip-capable streams — shared pair fold,
odd member flips every uniform — cutting CI width at the same S.

**Persistent stepper & async ingestion** — the ``stream=True`` drivers
(simulation and the checkpointed-DP forward pass) are thin loops over ONE
persistent ``FleetStepper``: a pre-compiled slab step from the
module-level ``functools.lru_cache`` factories, with the ``(state,
accumulator)`` carry and the incoming slab buffers donated back to XLA
every call (``jax.jit(donate_argnums=...)``), so advancing a fleet one
chunk at a time triggers **zero retraces** after warmup and never copies
the carry.  Conventions new code must preserve:

  * every streamed step factory stays module-level and lru-cached with
    ``donate`` in its key — a stepper LOOKS UP its compiled step, so
    constructing steppers (or calling ``run_fleet(stream=True)``
    repeatedly) never retraces a warm config;
  * ``donate=True`` callers must never retain a reference to a carry or
    slab after passing it in (the buffer is invalidated); paths that must
    retain old carries — the ``collect_schedule=True`` DP forward, which
    checkpoints them for the backtrack — pass ``donate=False``;
  * the traced step bodies bump ``STREAM_TRACES``, keeping the
    zero-retrace claim a tested invariant (tests/test_fleet_stepper.py),
    and donation must never break the bit-identity suites.

``async_ingest=True`` (streamed obs-backed paths) swaps the inline slab
build for ``core.ingest.SlabPrefetcher``: a double-buffered daemon thread
prepares slab n+1 (host slicing, dtype casts, the host->device put) while
the device executes slab n — XLA execute releases the GIL, so host work
overlaps device compute instead of serializing with it.  Bit-identical to
the synchronous loop by construction (same slabs, same order; asserted in
the ``stream_overlap`` bench row).  ``fleet_stepper`` exposes the same
machinery as a public long-lived API for live serving
(``serve.scheduler.LiveFleetScheduler``): admit per-instance telemetry
one slab at a time, read back per-instance hosting levels/fractions, zero
recompiles at any step count.

**Policy fan-out** — ``run_fleet`` (and ``fleet_stepper``) accept a
*sequence* of policies: each generated [B, chunk] obs slab is produced
exactly ONCE per scan step and every policy *lane* steps against it inside
the same compiled program — plus, with ``with_opt_forward=True``, the
offline DP's [B, K] entry frontier per lane, so a whole competitive-ratio
panel (every online family AND the OPT denominators) prices one shared
sample path in a single generation pass.  Conventions:

  * a **lane** is a ``PolicyFns`` (scored on the fleet's own grid) or a
    ``policies.base.PolicyLane`` binding the pair to its own accounting
    grid (e.g. the endpoint restriction for RR) plus — mandatory for
    Model-2 service, where the slab is generated on the fleet grid — a
    [B, K_lane] ``svc_cols`` column map (``HostingGrid.endpoint_columns``
    builds the endpoint one).  This check is the policy-axis home of the
    old ``fused_policy_families`` same-stream-family validation: lanes
    share the stream *by construction*, the engine only verifies each
    lane can price it;
  * lane states are heterogeneous (different policies, different K), so
    the carry holds a TUPLE of per-lane ``(state, acc)`` pytrees and each
    lane runs literally its own ``sim_chunk_core`` call over the shared
    slab (``simulator.sim_chunk_lanes``) — identical op chain, identical
    in-carry reduction order, per-lane ``freeze_invalid`` — which is why
    ``policies=[p]`` fan-out == standalone ``run_fleet(p)`` and lane ``p``
    of a fan-out == its standalone restricted run hold *bitwise*, under
    every mesh x chunking x streaming x ``n_seeds`` x backend config
    (tests/test_policy_fanout.py);
  * ``with_opt_forward=True`` threads one DP frontier per lane (the
    lane's own lv/mask, ``dp_fwd_chunk`` — the exact chunk kernel every
    offline driver shares) through the same carry and returns
    ``FleetResult.opt_cost``, bit-identical to
    ``offline_opt_fleet(checkpointed=True, collect_schedule=False)`` on
    the matching restricted fleet;
  * results are **policy-major**: row ``(p * B + b) * S + s``; reshape
    with ``FleetResult.policy_view`` ([P, B*S] leading axes), then
    ``seed_view`` per policy.  Compile-cache keys grow the tuple of
    per-lane ``(init_fn, step_fn)`` pairs — fan-out factories stay
    module-level and lru-cached like every other core.

**Multi-host fleets** — with ``jax.distributed`` initialized
(``repro.sharding.distributed.initialize()``), the ``fleet`` mesh spans
every process and the instance axis is bounded by aggregate host RAM.
Conventions:

  * **Global vs local B.**  Callers pass PROCESS-LOCAL inputs: each
    process constructs a ``FleetBatch`` / policy / scenario holding only
    its own ``B_local`` rows (the same ``B_local`` on every process),
    and owns global rows ``[p * B_pad_local, (p + 1) * B_pad_local)`` —
    the mesh orders devices process-contiguously, and padding to a device
    multiple happens per process (``_prepare_fleet``), which makes the
    global pad a global-device multiple automatically.  Counter-keyed
    scenarios make shard construction trivially consistent: build the
    global key set, keep your ``B_local`` slice.
  * **Who feeds which slab shard.**  Every obs path assembles global
    arrays with ``jax.make_array_from_process_local_data``
    (``_dev_rows``): each host device-puts only its own ``[B_local, ...]``
    rows — slab ingestion (``_obs_slab_builder`` -> ``slab_feed`` /
    ``SlabPrefetcher``), stepper telemetry (``FleetStepper.step``), and
    whole-horizon transfers alike ship ZERO cross-host observation bytes.
    The compiled cores are unchanged: ``shard_map`` over the fleet axis
    has no collectives, so per-row compute is process-local by
    construction and N-process == 1-process bit-identity holds row for
    row (tests/test_multihost.py).
  * **``gather=`` semantics.**  Results (and stepper readbacks) default
    to process-local views — this process's ``B_local`` rows, matching
    its inputs.  ``gather=True`` allgathers the full ``[B_global]`` rows
    onto every process (one cross-host collective per array, the only
    cross-host traffic in the engine); it is a no-op on single-process
    meshes, so library code can pass it through unconditionally.

**Names in a profiler trace** — host spans (``jax.profiler``
annotations, free when no trace is taken) mark the host's part of each
call: ``repro.step.put`` / ``.dispatch`` / ``.readback`` in
``FleetStepper`` (each with the stepped ``slot``) and
``repro.fleet.dispatch`` / ``.readback`` around the whole-horizon cores of
``run_fleet`` and ``offline_opt_fleet``.  On the device, ``jax.named_scope``
puts ``gen_arrivals`` / ``gen_rents`` (the combined scenario
``chunk_fn``), ``policy_step`` (``sim_chunk_core``) and ``dp_fwd``
(``dp_fwd_chunk``) in each op's ``op_name`` metadata; scopes change no op
(tests/test_trace_names.py).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.costs import HostingCosts, HostingGrid, default_float_dtype
from repro.core.ingest import slab_feed
from repro.core.policies.base import PolicyFns, PolicyLane, as_policy_lanes
from repro.core.policies.offline_opt import (DP_BACKENDS, dp_backtrack,
                                             dp_backtrack_chunk,
                                             dp_fetch_matrix, dp_frontier0,
                                             dp_fwd_chunk)
from repro.core.scenarios.base import PRNG_BACKENDS, Scenario, chunk_geometry
from repro.core.scenarios.combinators import (replicate_seeds,
                                              with_prng_backend)
from repro.core.simulator import (SimResult, sim_acc0, sim_chunk_core,
                                  sim_chunk_lanes, schedule_chunk_core)
from repro.sharding.context import shard_ctx
from repro.sharding.specs import (FLEET_AXIS, fleet_mesh,
                                  mesh_is_multiprocess,
                                  mesh_local_device_count,
                                  mesh_process_count)


def _span(name: str, **args):
    """A host span ``repro.<name>`` in the profiler's trace, on the same
    clock as the device's ops (about a microsecond when no trace is being
    taken)."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)


def _dispatch(mesh, core, *args):
    """Call a compiled whole-horizon core under the fleet's sharding
    context.  It returns once the program is enqueued; the readback of its
    results waits for the device."""
    with _span("fleet.dispatch"), shard_ctx(mesh, (FLEET_AXIS,),
                                            model_axis=None):
        return core(*args)


def _fleet_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` over the ``fleet`` mesh with the varying-axes
    check off.  Every core maps P(fleet) rows to P(fleet) rows with no
    collective, so there is no replicated output to verify; the check
    would only demand a ``pcast`` on every scan carry the core builds from
    constants (DP frontiers, accumulators, policy and generator state)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ----------------------------------------------------------------------
# FleetBatch: stacked instances + per-instance horizons.
# ----------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FleetBatch:
    """B hosting instances stacked with per-instance horizons.

    Attributes:
      grid: stacked ``HostingGrid`` (K-padding conventions live there).
      x:    [B, T_max] int32 arrivals, zero-padded past each instance's T —
            or None for a scenario-driven fleet (``for_scenario``), whose
            observations are generated on device inside the scan.
      c:    [B, T_max] rent costs, zero-padded (None with a scenario).
      T:    [B] int32 per-instance horizons (T_i <= T_max).
      svc:  optional [B, T_max, K] realized Model-2 service costs; None means
            Model 1 (``g * x``), computed chunk-by-chunk on device so it is
            never materialised for the whole horizon.
      side: optional [B, T_max] int32 side-channel (e.g. Markov state).

    Slots with ``t >= T_i`` are *invalid*: the engine freezes policy state
    and accumulates exactly zero cost there, so padded tails never affect an
    instance (the padding values themselves are arbitrary).
    """

    grid: HostingGrid
    x: Optional[jnp.ndarray]
    c: Optional[jnp.ndarray]
    T: jnp.ndarray
    svc: Optional[jnp.ndarray] = None
    side: Optional[jnp.ndarray] = None

    # ---- pytree protocol ---------------------------------------------
    def tree_flatten(self):
        return (self.grid, self.x, self.c, self.T, self.svc, self.side), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # ---- constructors -------------------------------------------------
    # Obs arrays are built HOST-resident (numpy): the compiled device paths
    # transfer them at the jit boundary anyway, and the streaming driver
    # must be able to slab-feed a horizon that never fits on device.

    @staticmethod
    def from_instances(costs_list: Sequence[HostingCosts], xs, cs,
                       svcs=None, sides=None) -> "FleetBatch":
        """Stack per-instance traces of *mixed lengths* (lists of [T_i]
        arrays; ``svcs`` entries are [T_i, K_i]), padding T and K."""
        grid = HostingGrid.from_costs(costs_list)
        dt = default_float_dtype()
        B, K = grid.B, grid.K
        lens = [int(np.shape(xi)[0]) for xi in xs]
        T_max = max(lens)
        x = np.zeros((B, T_max), np.int32)
        c = np.zeros((B, T_max), dt)
        svc = None if svcs is None else np.zeros((B, T_max, K), dt)
        side = None if sides is None else np.zeros((B, T_max), np.int32)
        for i in range(B):
            x[i, :lens[i]] = np.asarray(xs[i])
            c[i, :lens[i]] = np.asarray(cs[i])
            if svcs is not None:
                si = np.asarray(svcs[i])
                svc[i, :lens[i], :si.shape[1]] = si
            if sides is not None:
                side[i, :lens[i]] = np.asarray(sides[i])
        return FleetBatch(grid=grid, x=x, c=c,
                          T=np.asarray(lens, np.int32), svc=svc, side=side)

    @staticmethod
    def from_dense(grid: HostingGrid, x, c, svc=None, side=None,
                   T=None) -> "FleetBatch":
        """Wrap already-stacked [B, T] (or broadcastable [T]) observations;
        ``T`` defaults to the uniform full horizon."""
        dt = default_float_dtype()
        B = grid.B
        x = np.asarray(x, np.int32)
        if x.ndim == 1:
            x = np.broadcast_to(x[None, :], (B, x.shape[0]))
        T_max = x.shape[1]
        c = np.asarray(c, dt)
        if c.ndim == 1:
            c = np.broadcast_to(c[None, :], (B, T_max))
        if svc is not None:
            svc = np.asarray(svc, dt)
            if svc.ndim == 2:
                svc = np.broadcast_to(svc[None], (B,) + svc.shape)
        if side is not None:
            side = np.asarray(side, np.int32)
            if side.ndim == 1:
                side = np.broadcast_to(side[None, :], (B, T_max))
        if T is None:
            T = np.full((B,), T_max, np.int32)
        else:
            T = np.broadcast_to(np.asarray(T, np.int32), (B,))
        return FleetBatch(grid=grid, x=x, c=c, T=T, svc=svc, side=side)

    @staticmethod
    def for_scenario(grid: HostingGrid, T) -> "FleetBatch":
        """A fleet with NO materialized observations: pass the matching
        ``scenario=...`` to the engine entry points and the obs are
        generated on device inside the scan.  ``T`` is a scalar or [B]
        per-instance horizon vector."""
        T = np.broadcast_to(np.asarray(T, np.int32), (grid.B,))
        return FleetBatch(grid=grid, x=None, c=None, T=T)

    @staticmethod
    def from_scenario(grid: HostingGrid, scenario: Scenario, T,
                      chunk_size: Optional[int] = None) -> "FleetBatch":
        """Materialize a scenario into a classic obs-backed fleet (the
        reference the fused path is proven bit-identical against)."""
        from repro.core.scenarios.base import materialize
        T = np.broadcast_to(np.asarray(T, np.int32), (grid.B,))
        x, c, svc, side = materialize(scenario, int(T.max()), chunk_size)
        return FleetBatch.from_dense(grid, x, c, svc=svc, side=side, T=T)

    # ---- derived ------------------------------------------------------
    @property
    def B(self) -> int:
        return self.grid.B

    @property
    def K(self) -> int:
        return self.grid.K

    @property
    def T_max(self) -> int:
        if self.x is None:
            return int(np.max(np.asarray(self.T)))
        return self.x.shape[1]

    def restrict_to_endpoints(self) -> "FleetBatch":
        """The no-partial-hosting view (RR / OPT): 2-level grid, service
        costs gathered down to the (0, top) columns.  The gather runs in
        numpy so a host-resident svc stays on the host (same values as
        ``HostingGrid.endpoint_service``, which works on device arrays)."""
        svc2 = None
        if self.svc is not None:
            svc = np.asarray(self.svc)
            top = np.asarray(self.grid.top_index())          # [B]
            hi = np.take_along_axis(
                svc, np.broadcast_to(top[:, None, None],
                                     svc.shape[:2] + (1,)), axis=2)
            svc2 = np.concatenate([svc[:, :, :1], hi], axis=2)
        return FleetBatch(grid=self.grid.restrict_to_endpoints(),
                          x=self.x, c=self.c, T=self.T, svc=svc2,
                          side=self.side)


def _pad_rows(a, B_pad, xp=jnp):
    """Pad the leading [B] axis to B_pad by replicating row 0 (the padded
    rows run with T=0, so their contents never matter)."""
    B = a.shape[0]
    if B == B_pad:
        return a
    rep = xp.broadcast_to(a[:1], (B_pad - B,) + a.shape[1:])
    return xp.concatenate([a, rep], axis=0)


def _pad_fleet(fleet: FleetBatch, B_pad: int, T_pad: int) -> FleetBatch:
    """Pad instances to ``B_pad`` (dummy rows, T=0) and the time axis to
    ``T_pad`` (invalid tail slots).

    Obs padding runs in numpy so host-resident obs STAY on the host — the
    compiled drivers transfer whole [B, T] blocks at the jit boundary, and
    the streaming driver must never move more than one slab to the device.
    The (small) grid stays a device pytree.  Scenario-driven fleets
    (``x is None``) have no obs to pad — only the grid and T rows.
    """
    x = None if fleet.x is None else np.asarray(fleet.x)
    c = None if fleet.c is None else np.asarray(fleet.c)
    T = np.asarray(fleet.T)
    svc = None if fleet.svc is None else np.asarray(fleet.svc)
    side = None if fleet.side is None else np.asarray(fleet.side)
    if T_pad > fleet.T_max and x is not None:
        dt_pad = T_pad - fleet.T_max
        x = np.pad(x, ((0, 0), (0, dt_pad)))
        c = np.pad(c, ((0, 0), (0, dt_pad)))
        if svc is not None:
            svc = np.pad(svc, ((0, 0), (0, dt_pad), (0, 0)))
        if side is not None:
            side = np.pad(side, ((0, 0), (0, dt_pad)))
    if B_pad > fleet.B:
        grid = HostingGrid(M=_pad_rows(fleet.grid.M, B_pad),
                           levels=_pad_rows(fleet.grid.levels, B_pad),
                           g=_pad_rows(fleet.grid.g, B_pad),
                           mask=_pad_rows(fleet.grid.mask, B_pad))
        if x is not None:
            x = _pad_rows(x, B_pad, np)
            c = _pad_rows(c, B_pad, np)
        T = np.concatenate([T, np.zeros((B_pad - fleet.B,), np.int32)])
        if svc is not None:
            svc = _pad_rows(svc, B_pad, np)
        if side is not None:
            side = _pad_rows(side, B_pad, np)
    else:
        grid = fleet.grid
    return FleetBatch(grid=grid, x=x, c=c, T=T, svc=svc, side=side)


def _prepare_fleet(fleet: FleetBatch, mesh: Optional[Mesh],
                   chunk_size: Optional[int]):
    """Shared prologue of every fleet entry point: resolve the mesh, pad B
    to a device multiple (dummy T=0 instances) and T to a chunk multiple.
    Returns ``(mesh, padded fleet, n_chunks, T_pad)`` — T_pad is explicit
    because scenario-driven fleets carry no obs array to read it from."""
    mesh = fleet_mesh() if mesh is None else mesh
    if mesh_is_multiprocess(mesh):
        # Each process holds only its own [B_local] rows; pad them to a
        # multiple of the LOCAL device count.  Because the mesh orders
        # devices process-contiguously (fleet_mesh sorts on process_index)
        # and every process contributes the same device count, the global
        # pad is automatically a global-device multiple and process p's
        # rows are global rows [p * B_pad_local, (p + 1) * B_pad_local).
        n_dev = mesh_local_device_count(mesh)
    else:
        n_dev = int(mesh.devices.size)
    B_pad = math.ceil(fleet.B / n_dev) * n_dev
    n_chunks, T_pad = chunk_geometry(fleet.T_max, chunk_size)
    return mesh, _pad_fleet(fleet, B_pad, T_pad), n_chunks, T_pad


# ----------------------------------------------------------------------
# Multi-host data movement: process-local rows <-> globally-sharded arrays.
# Every helper is an exact single-process no-op, so the 1-process code
# paths stay byte-for-byte what they were.
# ----------------------------------------------------------------------

def _dev_rows(mesh, a):
    """Device-put a [B_pad_local, ...] row block for this mesh: plain
    ``jnp.asarray`` on a single-process mesh; on a process-spanning mesh, a
    globally-sharded ``jax.Array`` assembled with
    ``jax.make_array_from_process_local_data`` (this process contributes
    only its own rows — zero cross-host bytes, the sharding metadata is the
    only thing every process agrees on)."""
    if not mesh_is_multiprocess(mesh):
        return jnp.asarray(a)
    a = np.asarray(a)
    sharding = NamedSharding(mesh, P(FLEET_AXIS))
    gshape = (a.shape[0] * mesh_process_count(mesh),) + a.shape[1:]
    return jax.make_array_from_process_local_data(sharding, a, gshape)


def _dev_tree(mesh, tree):
    """``_dev_rows`` over every [B]-leading leaf of a params pytree."""
    return jax.tree_util.tree_map(lambda a: _dev_rows(mesh, a), tree)


def _dev_replicated(mesh, a):
    """Device-put a replicated (P()) input: committed locally on a
    single-process mesh; left an UNCOMMITTED host value on a multi-process
    mesh, where jit treats it as same-on-every-process replicated data (a
    locally-committed array would be rejected by a multi-process jit)."""
    return np.asarray(a) if mesh_is_multiprocess(mesh) else jnp.asarray(a)


def _local_rows(a):
    """Host view of this process's rows: for a non-fully-addressable global
    array, the process-local shards concatenated in global row order
    ([B_pad_local, ...]); otherwise plain ``np.asarray``."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        shards = sorted(a.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    return np.asarray(a)


def _gather_rows(mesh, a):
    """The ``gather=True`` opt-in: allgather process-local result rows to
    the full [B_global, ...] array on every process (one cross-host
    collective per array).  A no-op on single-process meshes and None."""
    if a is None or not mesh_is_multiprocess(mesh):
        return a
    from jax.experimental import multihost_utils
    # Gather the raw BIT PATTERN: a uint8 view widens the last axis by
    # itemsize, so the allgather never routes float64/int64 values through
    # jax's x64-disabled canonicalization (which would silently downcast —
    # gather=True must be dtype- and bit-exact).
    a = np.ascontiguousarray(a)
    out = np.asarray(multihost_utils.process_allgather(
        a.view(np.uint8), tiled=True))
    return out.view(a.dtype)


def _gather_result(res: "FleetResult", mesh) -> "FleetResult":
    g = lambda a: _gather_rows(mesh, a)
    return dataclasses.replace(
        res, total=g(res.total), fetch=g(res.fetch), rent=g(res.rent),
        service=g(res.service), r_hist=g(res.r_hist),
        level_slots=g(res.level_slots), T=g(res.T),
        opt_cost=g(res.opt_cost))


def _vmap_init(init_fn, params, mesh):
    """``vmap(init_fn)`` over [B]-stacked params with the output sharded
    like the inputs — on a process-spanning mesh the vmapped init runs
    under ``shard_map`` so every state leaf comes out P(fleet)-sharded
    (ready for the compiled step's in_specs with no resharding)."""
    if mesh_is_multiprocess(mesh):
        f = _fleet_map(jax.vmap(init_fn), mesh=mesh, in_specs=(P(FLEET_AXIS),),
                       out_specs=P(FLEET_AXIS))
        return jax.jit(f)(params)
    return jax.jit(jax.vmap(init_fn))(params)


# ----------------------------------------------------------------------
# Results.
# ----------------------------------------------------------------------

@dataclasses.dataclass
class FleetResult:
    """[B]-structured results of one fleet simulation (padded instances and
    padded time already sliced away).

    With a Monte-Carlo axis (``n_seeds=S``) the row axis is the flattened
    [B_instances * S] replication, instance-major and seed-minor: row
    ``b * S + s`` is instance ``b`` under seed ``s``.  ``seed_view``
    reshapes any such array to [B_instances, S, ...].

    With a policy fan-out axis (``n_policies=P > 1``) the row axis is
    additionally POLICY-MAJOR: row ``(p * B_fleet + b) * S + s`` is lane
    ``p`` on fleet row ``b`` under seed ``s``.  ``policy_view`` peels the
    lane axis off any [P * B_fleet * S]-leading array (after which
    ``seed_view`` applies per lane); ``level_slots`` of hetero-K lanes are
    zero-padded to the widest lane's K, and ``opt_cost`` carries the
    co-executed per-lane DP optimum when run with ``with_opt_forward=True``.
    """

    total: np.ndarray         # [B]
    fetch: np.ndarray         # [B]
    rent: np.ndarray          # [B]
    service: np.ndarray       # [B]
    r_hist: Optional[np.ndarray]  # [B, T_max] (frozen past each T_i); None
                                  # when run with collect_trace=False
    level_slots: np.ndarray   # [B, K] slots spent at each level
    T: np.ndarray             # [B] per-instance horizons
    n_seeds: int = 1          # MC replicas per instance (B = B_instances * S)
    n_policies: int = 1       # fan-out lanes (B = P * B_fleet * S)
    opt_cost: Optional[np.ndarray] = None  # [B] offline DP optimum per row
                                           # (with_opt_forward=True only)

    @property
    def B(self) -> int:
        return self.total.shape[0]

    @property
    def B_instances(self) -> int:
        """Distinct instances (the pre-replication B; includes the policy
        axis when fanned out — peel that off first with ``policy_view``)."""
        return self.B // self.n_seeds

    def seed_view(self, a) -> np.ndarray:
        """Reshape a [B*S]-leading result array to [B_instances, S, ...]."""
        a = np.asarray(a)
        return a.reshape((self.B_instances, self.n_seeds) + a.shape[1:])

    def policy_view(self, a) -> np.ndarray:
        """Reshape a policy-major [P * B_fleet * S]-leading result array to
        [P, B_fleet * S, ...] — one row block per fan-out lane."""
        a = np.asarray(a)
        return a.reshape((self.n_policies, self.B // self.n_policies)
                         + a.shape[1:])

    @property
    def per_slot(self) -> np.ndarray:
        return self.total / self.T

    def instance(self, i: int) -> SimResult:
        if self.r_hist is None:
            raise ValueError("no r_hist: fleet ran with collect_trace=False")
        return SimResult(total=float(self.total[i]), fetch=float(self.fetch[i]),
                         rent=float(self.rent[i]), service=float(self.service[i]),
                         r_hist=self.r_hist[i, :int(self.T[i])],
                         level_slots=self.level_slots[i])


@dataclasses.dataclass
class FleetOfflineResult:
    cost: np.ndarray                    # [B]
    r_hist: Optional[np.ndarray]        # [B, T_max]; None when the DP ran
                                        # with collect_schedule=False
    sim: Optional[FleetResult]          # None with collect_schedule=False
    n_seeds: int = 1

    def seed_view(self, a) -> np.ndarray:
        """Reshape a [B*S]-leading result array to [B_instances, S, ...]."""
        a = np.asarray(a)
        B = self.cost.shape[0] // self.n_seeds
        return a.reshape((B, self.n_seeds) + a.shape[1:])


def _fleet_result(r_hist, sums, counts, B, T_max, T,
                  n_seeds: int = 1) -> FleetResult:
    # float64 host accumulation, matching run_policy_batch; on a
    # multi-process mesh the device arrays read back as THIS process's rows
    # (_local_rows), so B here is the process-local row count
    sums = _local_rows(sums)[:B].astype(np.float64)
    return FleetResult(
        total=sums.sum(axis=1),
        rent=sums[:, 0], service=sums[:, 1], fetch=sums[:, 2],
        r_hist=None if r_hist is None else _local_rows(r_hist)[:B, :T_max],
        level_slots=_local_rows(counts)[:B].astype(np.int64),
        T=np.asarray(T).astype(np.int64), n_seeds=n_seeds)


def _fanout_result(r_lanes, sums_lanes, counts_lanes, opt_lanes,
                   B, T_max, T, n_seeds, mesh, gather=False) -> FleetResult:
    """Policy-major assembly of a fan-out run: each lane's device rows are
    sliced to this process's B rows exactly as ``_fleet_result`` does
    (identical casts, identical reduction order — lane p of the result is
    bitwise the standalone result), then concatenated along the row axis.
    On a process-spanning mesh ``gather=True`` allgathers PER LANE before
    concatenating — gathering the concatenated rows would interleave
    processes into the policy-major layout.  ``level_slots`` of hetero-K
    lanes are zero-padded to the widest lane's K."""
    gr = (lambda a: _gather_rows(mesh, a)) if gather else (lambda a: a)
    P_n = len(sums_lanes)
    sums = np.concatenate(
        [gr(_local_rows(s)[:B].astype(np.float64)) for s in sums_lanes])
    counts = [gr(_local_rows(cnt)[:B].astype(np.int64))
              for cnt in counts_lanes]
    K_max = max(cnt.shape[1] for cnt in counts)
    counts = np.concatenate(
        [np.pad(cnt, ((0, 0), (0, K_max - cnt.shape[1]))) for cnt in counts])
    r_hist = None
    if r_lanes is not None:
        r_hist = np.concatenate(
            [gr(np.ascontiguousarray(_local_rows(r)[:B, :T_max]))
             for r in r_lanes])
    opt_cost = None
    if opt_lanes is not None:
        opt_cost = np.concatenate(
            [gr(_local_rows(o)[:B].astype(np.float64)) for o in opt_lanes])
    T_rows = gr(np.asarray(T).astype(np.int64))
    return FleetResult(
        total=sums.sum(axis=1), rent=sums[:, 0], service=sums[:, 1],
        fetch=sums[:, 2], r_hist=r_hist, level_slots=counts,
        T=np.tile(T_rows, P_n), n_seeds=n_seeds, n_policies=P_n,
        opt_cost=opt_cost)


# ----------------------------------------------------------------------
# Monte-Carlo summary over the seed axis.
# ----------------------------------------------------------------------

# two-sided 97.5% Student-t quantiles by degrees of freedom (n_seeds - 1);
# the normal 1.96 badly undercovers at the small n_seeds the sweeps use
_T975 = {1: 12.706, 2: 4.303, 3: 3.182, 4: 2.776, 5: 2.571, 6: 2.447,
         7: 2.365, 8: 2.306, 9: 2.262, 10: 2.228}


def student_t975(df: int) -> float:
    """Two-sided 97.5% Student-t quantile (95% CI width) for ``df`` degrees
    of freedom — the ONE table every MC aggregation path shares
    (``mc_summary`` here, ``benchmarks.common.mc_aggregate``)."""
    if df in _T975:
        return _T975[df]
    return 2.04 if df <= 30 else 1.96


def mc_stats(v, axis: int = -1):
    """(mean, ci95 half-width) over the seed axis of ``v`` (Student-t, same
    quantiles as ``mc_summary``); ci95 is zeros when that axis has one
    sample."""
    v = np.asarray(v, np.float64)
    S = v.shape[axis]
    mean = v.mean(axis=axis)
    if S <= 1:
        return mean, np.zeros_like(mean)
    ci = student_t975(S - 1) * v.std(axis=axis, ddof=1) / math.sqrt(S)
    return mean, ci


def mc_summary(result, fields=("total", "rent", "service", "fetch"),
               antithetic: bool = False):
    """Collapse a seed-replicated result's MC axis into arrays.

    Accepts a ``FleetResult`` (or ``FleetOfflineResult``, whose summarised
    field is ``cost``) produced with ``n_seeds=S``.  Returns a dict with
    ``n_seeds`` plus, per field, ``<f>_mean`` and ``<f>_ci95`` arrays of
    shape [B_instances] — the per-instance seed-mean and the two-sided 95%
    Student-t CI half-width (zeros at S == 1).

    ``antithetic=True`` (for results of ``run_fleet(...,
    antithetic=True)``) averages each replica pair (2m, 2m+1) into one
    pair-mean before the CI — the pairs are negatively correlated by
    construction, so the naive S-sample formula badly overstates the
    estimator's width; the S/2 pair-means are independent and give the
    valid (and much tighter) interval.  The reported mean is unchanged.
    """
    if isinstance(result, FleetOfflineResult):
        fields = tuple(f if f != "total" else "cost" for f in fields
                       if f in ("total", "cost"))
    if antithetic and result.n_seeds % 2:
        raise ValueError("antithetic summary needs an even n_seeds")
    out = {"n_seeds": result.n_seeds}
    for f in fields:
        v = result.seed_view(getattr(result, f))
        if antithetic:
            v = np.asarray(v, np.float64)
            v = (v[:, 0::2] + v[:, 1::2]) / 2.0
        mean, ci = mc_stats(v, axis=1)
        out[f"{f}_mean"] = mean
        out[f"{f}_ci95"] = ci
    return out


# ----------------------------------------------------------------------
# Compiled cores: vmap over instances, shard_map over the fleet axis.
# ----------------------------------------------------------------------

def _model1_svc(x, g):
    # identical elementwise to _batch_obs's full-horizon computation, so
    # computing it per chunk is bitwise equivalent
    return x[..., :, None].astype(g.dtype) * g[..., None, :]


def _chunked_drive(run_chunk, carry0, n_chunks: int, arrays):
    """The one chunk driver every fleet core shares (sim, DP fwd, schedule
    eval): cut each [T_pad, ...] array of ``arrays`` (None entries pass
    through) into ``n_chunks`` chunks, thread ``carry`` across them with an
    outer ``lax.scan``, and restitch the per-chunk ys.  ``run_chunk(carry,
    t0, *chunk_arrays) -> (carry', ys_chunk | None)``.  n_chunks == 1 calls
    ``run_chunk`` directly — chunked == unchunked is proven against that
    path, so keep any chunking change HERE, not in the cores."""
    T_pad = next(a for a in arrays if a is not None).shape[0]
    chunk = T_pad // n_chunks
    if n_chunks == 1:
        return run_chunk(carry0, jnp.asarray(0, jnp.int32), *arrays)
    xs = tuple(None if a is None
               else a.reshape((n_chunks, chunk) + a.shape[1:])
               for a in arrays)

    def outer(carry, inp):
        t0, *cks = inp
        return run_chunk(carry, t0, *cks)

    carry, ys = jax.lax.scan(
        outer, carry0, (jnp.arange(n_chunks, dtype=jnp.int32) * chunk,) + xs)
    if ys is not None:
        # ys may be a pytree (the fan-out cores emit one trace per lane);
        # for a single array the tree_map is the previous reshape verbatim
        ys = jax.tree_util.tree_map(
            lambda y: y.reshape((T_pad,) + y.shape[2:]), ys)
    return carry, ys


def _make_instance_core(init_fn, step_fn, include_final_fetch: bool,
                        n_chunks: int, has_svc: bool, has_side: bool,
                        collect_trace: bool = True):
    """Whole-horizon core for ONE instance: outer scan over T-chunks, inner
    ``sim_chunk_core`` per chunk.  Args: (params, lv, g, M, T_len, x, c
    [, svc][, side]) with [T_pad]-shaped obs, T_pad = n_chunks * chunk."""

    def core(params, lv, g, M, T_len, x, c, *opt):
        K = lv.shape[-1]
        svc = opt[0] if has_svc else None
        side = opt[1 if has_svc else 0] if has_side else None
        carry0 = (init_fn(params), sim_acc0(K, lv.dtype))

        def run_chunk(carry, t0, xck, cck, sck, sdck):
            if sck is None:
                sck = _model1_svc(xck, g)
            if sdck is None:
                sdck = jnp.zeros(xck.shape, jnp.int32)
            carry, r = sim_chunk_core(step_fn, include_final_fetch, params,
                                      lv, M, T_len, t0, carry, xck, cck,
                                      sck, sdck)
            return carry, (r if collect_trace else None)

        carry, r_hist = _chunked_drive(run_chunk, carry0, n_chunks,
                                       (x, c, svc, side))
        (_, acc) = carry
        if collect_trace:
            return r_hist, acc["sums"], acc["counts"]
        return acc["sums"], acc["counts"]

    return core


@functools.lru_cache(maxsize=64)
def _compiled_fleet_core(init_fn, step_fn, include_final_fetch: bool,
                         n_chunks: int, has_svc: bool, has_side: bool,
                         collect_trace: bool, mesh: Mesh):
    core = _make_instance_core(init_fn, step_fn, include_final_fetch,
                               n_chunks, has_svc, has_side, collect_trace)
    n_args = 7 + int(has_svc) + int(has_side)
    spec = P(FLEET_AXIS)
    n_out = 3 if collect_trace else 2
    sharded = _fleet_map(jax.vmap(core), mesh=mesh,
                         in_specs=(spec,) * n_args,
                         out_specs=(spec,) * n_out)
    return jax.jit(sharded)


def _slab_obs(slab, g):
    """Fill a generated slab's optional channels with the engine defaults
    (Model-1 service from the slab's own arrivals; zero side)."""
    svc = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
    side = (slab.side if slab.side is not None
            else jnp.zeros(slab.x.shape, jnp.int32))
    return slab.x, slab.c, svc, side


def _make_scenario_instance_core(init_fn, step_fn, sc_init, sc_chunk,
                                 include_final_fetch: bool, n_chunks: int,
                                 collect_trace: bool):
    """Fused core for ONE instance: the scenario's ``chunk_fn`` generates
    each [chunk] slab *inside* the outer scan (generator state threaded
    through the carry next to the policy state), then ``sim_chunk_core``
    consumes it.  Args: (pparams, sparams, lv, g, M, T_len, tids_all) where
    ``tids_all = arange(T_pad)`` is the only [T]-shaped input — replicated,
    never sharded, and the only thing resembling an obs array anywhere."""

    def core(pparams, sparams, lv, g, M, T_len, tids_all):
        K = lv.shape[-1]
        carry0 = (sc_init(sparams), (init_fn(pparams), sim_acc0(K, lv.dtype)))

        def run_chunk(carry, t0, tids):
            gen_state, sim = carry
            gen_state, slab = sc_chunk(sparams, gen_state, tids)
            x, c, svc, side = _slab_obs(slab, g)
            sim, r = sim_chunk_core(step_fn, include_final_fetch, pparams,
                                    lv, M, T_len, t0, sim, x, c, svc, side)
            return (gen_state, sim), (r if collect_trace else None)

        carry, r_hist = _chunked_drive(run_chunk, carry0, n_chunks,
                                       (tids_all,))
        (_, (_, acc)) = carry
        if collect_trace:
            return r_hist, acc["sums"], acc["counts"]
        return acc["sums"], acc["counts"]

    return core


@functools.lru_cache(maxsize=64)
def _compiled_scenario_core(init_fn, step_fn, sc_init, sc_chunk,
                            include_final_fetch: bool, n_chunks: int,
                            collect_trace: bool, mesh: Mesh):
    core = _make_scenario_instance_core(init_fn, step_fn, sc_init, sc_chunk,
                                        include_final_fetch, n_chunks,
                                        collect_trace)
    spec = P(FLEET_AXIS)
    n_out = 3 if collect_trace else 2
    sharded = _fleet_map(
        jax.vmap(core, in_axes=(0, 0, 0, 0, 0, 0, None)), mesh=mesh,
        in_specs=(spec,) * 6 + (P(),), out_specs=(spec,) * n_out)
    return jax.jit(sharded)


# test hook: Python trace counts per streamed-step family.  The factories'
# step bodies bump their entry when (and only when) jax traces them, so
# ``sum(STREAM_TRACES.values())`` staying flat across N stepper steps IS
# the zero-retrace proof (tests/test_fleet_stepper.py asserts it).
# Donation is best-effort: on backends where a donated slab's shape can't
# alias any output (e.g. CPU host buffers of [B, chunk] telemetry) XLA
# simply skips the aliasing — correct, just not reusable.  Silence the
# advisory warning that would otherwise fire at every trace.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

STREAM_TRACES = collections.Counter()


@functools.lru_cache(maxsize=64)
def _compiled_stream_step(init_fn, step_fn, include_final_fetch: bool,
                          has_svc: bool, has_side: bool, mesh: Mesh,
                          donate: bool = False):
    """One [B, chunk] slab: (carry, chunk obs) -> (carry', r_chunk).  The
    host streaming loop drives this; device memory stays O(B * chunk).
    ``donate=True`` donates the carry and the incoming slab buffers to XLA
    (the caller must not reuse them — the stepper contract)."""

    def step(params, lv, g, M, T_len, t0, carry, xck, cck, *opt):
        STREAM_TRACES["sim_obs"] += 1
        sck = opt[0] if has_svc else _model1_svc(xck, g)
        sdck = (opt[1 if has_svc else 0] if has_side
                else jnp.zeros(xck.shape, jnp.int32))
        return sim_chunk_core(step_fn, include_final_fetch, params, lv, M,
                              T_len, t0, carry, xck, cck, sck, sdck)

    n_opt = int(has_svc) + int(has_side)
    in_axes = (0, 0, 0, 0, 0, None, 0, 0, 0) + (0,) * n_opt
    spec = P(FLEET_AXIS)
    in_specs = (spec,) * 5 + (P(),) + (spec,) * (3 + n_opt)
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes, out_axes=(0, 0)),
                         mesh=mesh, in_specs=in_specs, out_specs=(spec, spec))
    donate_argnums = tuple(range(6, 9 + n_opt)) if donate else ()
    return jax.jit(sharded, donate_argnums=donate_argnums)


@functools.lru_cache(maxsize=64)
def _compiled_scenario_stream_step(init_fn, step_fn, sc_init, sc_chunk,
                                   include_final_fetch: bool, chunk: int,
                                   collect_trace: bool, mesh: Mesh,
                                   donate: bool = False):
    """One fused-generation slab step for the host-driven streaming loop:
    the host ships a scalar chunk offset per iteration — zero observation
    bytes cross the host->device boundary.  ``donate=True`` donates the
    ``(gen_state, (policy state, acc))`` carry."""

    def step(pparams, sparams, lv, g, M, T_len, t0, carry):
        STREAM_TRACES["sim_scenario"] += 1
        tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
        gen_state, sim = carry
        gen_state, slab = sc_chunk(sparams, gen_state, tids)
        x, c, svc, side = _slab_obs(slab, g)
        sim, r = sim_chunk_core(step_fn, include_final_fetch, pparams, lv, M,
                                T_len, t0, sim, x, c, svc, side)
        carry = (gen_state, sim)
        return (carry, r) if collect_trace else carry

    spec = P(FLEET_AXIS)
    in_axes = (0, 0, 0, 0, 0, 0, None, 0)
    in_specs = (spec,) * 6 + (P(),) + (spec,)
    out_specs = (spec, spec) if collect_trace else spec
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes), mesh=mesh,
                         in_specs=in_specs, out_specs=out_specs)
    return jax.jit(sharded, donate_argnums=(7,) if donate else ())


# ----------------------------------------------------------------------
# Policy fan-out cores: ONE generated [chunk] slab per step, P policy
# lanes (and, with with_opt, P offline-DP frontiers) consuming it inside
# the same compiled program.  See "Policy fan-out" in the module
# docstring.  Each core takes ``lanes`` — the tuple of per-lane
# (params, lv, g, M, mask, cols) device rows (_lane_arrays) — and emits a
# FLAT tuple of outputs (explicit out_specs need a flat shape):
# P x r_hist (collect_trace) + P x sums + P x counts + P x opt (with_opt).
# ----------------------------------------------------------------------

def _lane_svc(svc, x, g, cols, own_grid: bool, i: int):
    """The [chunk, K_lane] service slab lane ``i`` prices: the shared slab
    itself (fleet-grid lane), its ``svc_cols`` gather (own-grid lane under
    Model 2 — coupled uniforms make the gathered columns bitwise equal to
    generating on the lane grid directly), or Model-1 pricing ``g * x``
    from the lane's own g row.  Structural mismatches raise at trace time —
    the scenario-fused twin of the eager ``_check_lanes`` validation."""
    if svc is None:
        if cols is not None:
            raise ValueError(
                f"fan-out lane {i}: svc_cols= was given but the stream "
                "generates no Model-2 service channel — a Model-1 lane "
                "prices g * x from its own grid")
        return _model1_svc(x, g)
    if cols is None:
        if own_grid:
            raise ValueError(
                f"fan-out lane {i}: a lane on its own grid must map the "
                "shared Model-2 service slab onto its levels via svc_cols= "
                "(the stream is generated ONCE, on the fleet grid — "
                "HostingGrid.endpoint_columns builds the endpoint map)")
        return svc
    return jnp.take(svc, cols, axis=-1)


def _lane_dp_grid(lanes):
    """Per-lane hoisted (lv32, fetch_mat, kmask) for the co-executed DP —
    the same prologue every offline core computes once per instance."""
    out = []
    for (_params, lv, _g, M, mask, _cols) in lanes:
        lv32 = lv.astype(jnp.float32)
        out.append((lv32, dp_fetch_matrix(M.astype(jnp.float32), lv32), mask))
    return tuple(out)


def _fanout_chunk(lane_fns, lane_own, include_final_fetch, with_opt,
                  dp_backend, lanes, dp_grid, T_len, t0, sims, Js,
                  x, c, svc, side):
    """Advance every lane (and optionally every DP frontier) over ONE
    shared slab — the body every fan-out driver shares.  Returns
    (sims', Js', per-lane r chunks)."""
    n_lanes = len(lane_fns)
    svcs = tuple(_lane_svc(svc, x, lanes[i][2], lanes[i][5], lane_own[i], i)
                 for i in range(n_lanes))
    sims, rs = sim_chunk_lanes(
        tuple(fns[1] for fns in lane_fns), include_final_fetch,
        tuple(l[0] for l in lanes), tuple(l[1] for l in lanes),
        tuple(l[3] for l in lanes), T_len, t0, sims, x, c, svcs, side)
    if with_opt:
        tids = t0 + jnp.arange(x.shape[-1], dtype=jnp.int32)
        Js = tuple(
            dp_fwd_chunk(J, tids, c, svck, lv32, kmask, fetch_mat,
                         T_len, dp_backend)[0]
            for J, (lv32, fetch_mat, kmask), svck in zip(Js, dp_grid, svcs))
    return sims, Js, rs


def _make_fanout_instance_core(lane_fns, lane_own, include_final_fetch: bool,
                               n_chunks: int, has_svc: bool, has_side: bool,
                               collect_trace: bool, with_opt: bool,
                               dp_backend: str):
    """Whole-horizon fan-out core for ONE instance, obs-backed.
    Args: (lanes, T_len, x, c[, svc][, side])."""
    n_lanes = len(lane_fns)

    def core(lanes, T_len, x, c, *opt):
        svc = opt[0] if has_svc else None
        side = opt[1 if has_svc else 0] if has_side else None
        sims0 = tuple(
            (fns[0](l[0]), sim_acc0(l[1].shape[-1], l[1].dtype))
            for fns, l in zip(lane_fns, lanes))
        dp_grid = _lane_dp_grid(lanes) if with_opt else None
        carry0 = ((sims0, tuple(dp_frontier0(l[1].shape[-1]) for l in lanes))
                  if with_opt else sims0)

        def run_chunk(carry, t0, xck, cck, sck, sdck):
            sims, Js = carry if with_opt else (carry, None)
            if sdck is None:
                sdck = jnp.zeros(xck.shape, jnp.int32)
            sims, Js, rs = _fanout_chunk(
                lane_fns, lane_own, include_final_fetch, with_opt,
                dp_backend, lanes, dp_grid, T_len, t0, sims, Js,
                xck, cck, sck, sdck)
            carry = (sims, Js) if with_opt else sims
            return carry, (rs if collect_trace else None)

        carry, r_hists = _chunked_drive(run_chunk, carry0, n_chunks,
                                        (x, c, svc, side))
        sims, Js = carry if with_opt else (carry, None)
        outs = tuple(r_hists) if collect_trace else ()
        outs += tuple(acc["sums"] for (_, acc) in sims)
        outs += tuple(acc["counts"] for (_, acc) in sims)
        if with_opt:
            outs += tuple(jnp.min(J) for J in Js)
        return outs

    return core


def _make_fanout_scenario_core(lane_fns, lane_own, sc_init, sc_chunk,
                               include_final_fetch: bool, n_chunks: int,
                               collect_trace: bool, with_opt: bool,
                               dp_backend: str):
    """Fused-generation fan-out core for ONE instance: the scenario's
    ``chunk_fn`` emits each [chunk] slab exactly once inside the scan and
    every lane consumes it.  Args: (lanes, sparams, T_len, tids_all)."""

    def core(lanes, sparams, T_len, tids_all):
        sims0 = tuple(
            (fns[0](l[0]), sim_acc0(l[1].shape[-1], l[1].dtype))
            for fns, l in zip(lane_fns, lanes))
        dp_grid = _lane_dp_grid(lanes) if with_opt else None
        carry0 = (sc_init(sparams), sims0)
        if with_opt:
            carry0 += (tuple(dp_frontier0(l[1].shape[-1]) for l in lanes),)

        def run_chunk(carry, t0, tids):
            gen_state, sims = carry[0], carry[1]
            Js = carry[2] if with_opt else None
            gen_state, slab = sc_chunk(sparams, gen_state, tids)
            side = (slab.side if slab.side is not None
                    else jnp.zeros(slab.x.shape, jnp.int32))
            sims, Js, rs = _fanout_chunk(
                lane_fns, lane_own, include_final_fetch, with_opt,
                dp_backend, lanes, dp_grid, T_len, t0, sims, Js,
                slab.x, slab.c, slab.svc, side)
            carry = (gen_state, sims) + ((Js,) if with_opt else ())
            return carry, (rs if collect_trace else None)

        carry, r_hists = _chunked_drive(run_chunk, carry0, n_chunks,
                                        (tids_all,))
        sims = carry[1]
        outs = tuple(r_hists) if collect_trace else ()
        outs += tuple(acc["sums"] for (_, acc) in sims)
        outs += tuple(acc["counts"] for (_, acc) in sims)
        if with_opt:
            outs += tuple(jnp.min(J) for J in carry[2])
        return outs

    return core


@functools.lru_cache(maxsize=32)
def _compiled_fanout_core(lane_fns, lane_own, include_final_fetch: bool,
                          n_chunks: int, has_svc: bool, has_side: bool,
                          collect_trace: bool, with_opt: bool,
                          dp_backend: str, mesh: Mesh):
    core = _make_fanout_instance_core(lane_fns, lane_own, include_final_fetch,
                                      n_chunks, has_svc, has_side,
                                      collect_trace, with_opt, dp_backend)
    n_lanes = len(lane_fns)
    spec = P(FLEET_AXIS)
    n_args = 4 + int(has_svc) + int(has_side)
    n_out = n_lanes * (2 + int(collect_trace) + int(with_opt))
    sharded = _fleet_map(jax.vmap(core), mesh=mesh,
                         in_specs=(spec,) * n_args,
                         out_specs=(spec,) * n_out)
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_fanout_scenario_core(lane_fns, lane_own, sc_init, sc_chunk,
                                   include_final_fetch: bool, n_chunks: int,
                                   collect_trace: bool, with_opt: bool,
                                   dp_backend: str, mesh: Mesh):
    core = _make_fanout_scenario_core(lane_fns, lane_own, sc_init, sc_chunk,
                                      include_final_fetch, n_chunks,
                                      collect_trace, with_opt, dp_backend)
    n_lanes = len(lane_fns)
    spec = P(FLEET_AXIS)
    n_out = n_lanes * (2 + int(collect_trace) + int(with_opt))
    sharded = _fleet_map(jax.vmap(core, in_axes=(0, 0, 0, None)), mesh=mesh,
                         in_specs=(spec, spec, spec, P()),
                         out_specs=(spec,) * n_out)
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_fanout_stream_step(lane_fns, lane_own,
                                 include_final_fetch: bool, has_svc: bool,
                                 has_side: bool, collect_trace: bool,
                                 with_opt: bool, dp_backend: str, mesh: Mesh,
                                 donate: bool = False):
    """One fan-out slab step for the host streaming loop: the shared
    [B, chunk] slab in, every lane's (state, acc) — and DP frontier with
    ``with_opt`` — advanced in one compiled call.  Carry: ``(sims,)`` or
    ``(sims, Js)``, tuples of per-lane pytrees."""

    def step(lanes, T_len, t0, carry, xck, cck, *opt):
        STREAM_TRACES["sim_obs_fanout"] += 1
        sck = opt[0] if has_svc else None
        sdck = (opt[1 if has_svc else 0] if has_side
                else jnp.zeros(xck.shape, jnp.int32))
        sims = carry[0]
        Js = carry[1] if with_opt else None
        dp_grid = _lane_dp_grid(lanes) if with_opt else None
        sims, Js, rs = _fanout_chunk(
            lane_fns, lane_own, include_final_fetch, with_opt, dp_backend,
            lanes, dp_grid, T_len, t0, sims, Js, xck, cck, sck, sdck)
        carry = (sims, Js) if with_opt else (sims,)
        return (carry, rs) if collect_trace else carry

    n_opt = int(has_svc) + int(has_side)
    in_axes = (0, 0, None, 0, 0, 0) + (0,) * n_opt
    spec = P(FLEET_AXIS)
    in_specs = (spec, spec, P(), spec, spec, spec) + (spec,) * n_opt
    out_specs = (spec, spec) if collect_trace else spec
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes), mesh=mesh,
                         in_specs=in_specs, out_specs=out_specs)
    donate_argnums = tuple(range(3, 6 + n_opt)) if donate else ()
    return jax.jit(sharded, donate_argnums=donate_argnums)


@functools.lru_cache(maxsize=32)
def _compiled_fanout_scenario_stream_step(lane_fns, lane_own, sc_init,
                                          sc_chunk,
                                          include_final_fetch: bool,
                                          chunk: int, collect_trace: bool,
                                          with_opt: bool, dp_backend: str,
                                          mesh: Mesh, donate: bool = False):
    """One fused-generation fan-out slab step: the host ships one scalar
    offset per chunk, the generator runs once, every lane consumes its
    slab.  Carry: ``(gen_state, sims[, Js])``."""

    def step(lanes, sparams, T_len, t0, carry):
        STREAM_TRACES["sim_scenario_fanout"] += 1
        tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
        gen_state, sims = carry[0], carry[1]
        Js = carry[2] if with_opt else None
        dp_grid = _lane_dp_grid(lanes) if with_opt else None
        gen_state, slab = sc_chunk(sparams, gen_state, tids)
        side = (slab.side if slab.side is not None
                else jnp.zeros(slab.x.shape, jnp.int32))
        sims, Js, rs = _fanout_chunk(
            lane_fns, lane_own, include_final_fetch, with_opt, dp_backend,
            lanes, dp_grid, T_len, t0, sims, Js, slab.x, slab.c, slab.svc,
            side)
        carry = (gen_state, sims) + ((Js,) if with_opt else ())
        return (carry, rs) if collect_trace else carry

    spec = P(FLEET_AXIS)
    in_axes = (0, 0, 0, None, 0)
    in_specs = (spec, spec, spec, P(), spec)
    out_specs = (spec, spec) if collect_trace else spec
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes), mesh=mesh,
                         in_specs=in_specs, out_specs=out_specs)
    return jax.jit(sharded, donate_argnums=(4,) if donate else ())


def _pad_params(params, B_pad: int):
    """Pad every [B]-leading leaf of a params pytree (policy or scenario)
    to B_pad by replicating row 0 (padded instances run with T = 0)."""
    return jax.tree_util.tree_map(
        lambda a: _pad_rows(jnp.asarray(a), B_pad), params)


def _policy_arrays(policy: PolicyFns, fleet: FleetBatch, B_pad: int, mesh):
    dt = default_float_dtype()
    params = _pad_params(policy.params, B_pad)
    lv = _pad_rows(fleet.grid.levels.astype(dt), B_pad)
    g = _pad_rows(fleet.grid.g.astype(dt), B_pad)
    M = _pad_rows(fleet.grid.M.astype(dt), B_pad)
    return (_dev_tree(mesh, params), _dev_rows(mesh, lv),
            _dev_rows(mesh, g), _dev_rows(mesh, M))


def _check_lanes(lanes, fleet: FleetBatch, has_svc: Optional[bool]):
    """Eager fan-out validation — the policy-axis home of the old
    ``fused_policy_families`` same-stream-family check: lanes share the
    stream by construction, the engine verifies each lane can PRICE it.
    ``has_svc`` is None when the service channel is only known at trace
    time (scenario-fused runs), where ``_lane_svc`` enforces the same
    rules on the generated slab's structure."""
    for i, lane in enumerate(lanes):
        if not isinstance(lane.fns, PolicyFns):
            raise TypeError(f"fan-out lane {i}: .fns must be a PolicyFns, "
                            f"got {type(lane.fns).__name__}")
        if lane.grid is not None and lane.grid.B != fleet.B:
            raise ValueError(
                f"fan-out lane {i} ({lane.name!r}): lane grid B="
                f"{lane.grid.B} != fleet B={fleet.B}")
        if lane.svc_cols is not None:
            if lane.grid is None:
                raise ValueError(
                    f"fan-out lane {i} ({lane.name!r}): svc_cols= without a "
                    "lane grid — a fleet-grid lane prices the shared svc "
                    "slab directly")
            if has_svc is False:
                raise ValueError(
                    f"fan-out lane {i} ({lane.name!r}): svc_cols= but the "
                    "fleet carries no Model-2 service channel — a Model-1 "
                    "lane prices g * x from its own grid")
            cols = np.asarray(lane.svc_cols)
            if cols.ndim != 2 or cols.shape[0] != fleet.B:
                raise ValueError(
                    f"fan-out lane {i} ({lane.name!r}): svc_cols must be "
                    f"[B={fleet.B}, K_lane], got shape {cols.shape}")
        elif lane.grid is not None and has_svc is True:
            raise ValueError(
                f"fan-out lane {i} ({lane.name!r}): a lane on its own grid "
                "must map the fleet's Model-2 service slab onto its levels "
                "via svc_cols= (HostingGrid.endpoint_columns builds the "
                "endpoint map)")


def _lane_arrays(lanes, padded: FleetBatch, S: int, mesh):
    """Per-lane device arg tuples of the fan-out cores — (params, lv, g, M,
    mask, cols) per lane, every row block seed-replicated (x S) and padded
    to the fleet's B_pad exactly as ``_policy_arrays``/``_replicate_mc`` do
    for the classic path.  Fleet-grid lanes (grid=None) reuse the padded
    fleet grid's rows untouched."""
    dt = default_float_dtype()
    B_pad = padded.B
    rep = lambda a: (jnp.asarray(a) if S == 1
                     else jnp.repeat(jnp.asarray(a), S, axis=0))
    out = []
    for lane in lanes:
        pol = _replicate_policy(lane.fns, S)
        params = _dev_tree(mesh, _pad_params(pol.params, B_pad))
        if lane.grid is None:
            grid, prep = padded.grid, (lambda a: a)
        else:
            grid, prep = lane.grid, (lambda a: _pad_rows(rep(a), B_pad))
        lv = _dev_rows(mesh, prep(grid.levels.astype(dt)))
        g = _dev_rows(mesh, prep(grid.g.astype(dt)))
        M = _dev_rows(mesh, prep(grid.M.astype(dt)))
        mask = _dev_rows(mesh, prep(grid.mask))
        cols = None
        if lane.svc_cols is not None:
            cols = _dev_rows(mesh, _pad_rows(
                rep(jnp.asarray(lane.svc_cols, jnp.int32)), B_pad))
        out.append((params, lv, g, M, mask, cols))
    return tuple(out)


def _check_scenario(scenario: Scenario, fleet: FleetBatch):
    if fleet.x is not None or fleet.c is not None:
        raise ValueError(
            "scenario=... needs an obs-less fleet (FleetBatch.for_scenario); "
            "materialized observations would be silently ignored")
    if scenario.B != fleet.B:
        raise ValueError(f"scenario B={scenario.B} != fleet B={fleet.B}")


def _replicate_mc(fleet: FleetBatch, scenario: Optional[Scenario],
                  n_seeds: Optional[int], antithetic: bool = False):
    """Expand a [B] fleet + scenario to the [B*S] Monte-Carlo replication
    (instance-major, seed-minor; seed folded into every stream key by
    ``replicate_seeds`` — ``antithetic=True`` pairs replicas (2m, 2m+1) on
    flip-capable streams).  Returns them unchanged when ``n_seeds`` is None.
    """
    if n_seeds is None:
        if antithetic:
            raise ValueError("antithetic=True needs n_seeds=")
        return fleet, scenario, 1
    if scenario is None:
        raise ValueError(
            "n_seeds= needs scenario=: materialized observations carry no "
            "seed axis to fold (stack replica rows yourself instead)")
    S = int(n_seeds)
    rep = lambda a: jnp.repeat(jnp.asarray(a), S, axis=0)
    grid = HostingGrid(M=rep(fleet.grid.M), levels=rep(fleet.grid.levels),
                       g=rep(fleet.grid.g), mask=rep(fleet.grid.mask))
    rfleet = FleetBatch(grid=grid, x=None, c=None,
                        T=np.repeat(np.asarray(fleet.T, np.int32), S))
    return rfleet, replicate_seeds(scenario, S, antithetic=antithetic), S


def _replicate_policy(policy: PolicyFns, S: int) -> PolicyFns:
    if S == 1:
        return policy
    return policy._replace(params=jax.tree_util.tree_map(
        lambda a: jnp.repeat(jnp.asarray(a), S, axis=0), policy.params))


def run_fleet(policy, fleet: FleetBatch, *,
              scenario: Optional[Scenario] = None,
              mesh: Optional[Mesh] = None, chunk_size: Optional[int] = None,
              include_final_fetch: bool = True,
              stream: bool = False, collect_trace: bool = True,
              n_seeds: Optional[int] = None,
              antithetic: bool = False,
              prng_backend: str = "xla",
              with_opt_forward: bool = False,
              dp_backend: str = "xla",
              async_ingest: bool = False,
              gather: bool = False) -> FleetResult:
    """Simulate a fleet: sharded over devices, chunked/streamed over time.

    Args:
      policy: pure-function policy batch whose params carry a leading [B]
        axis matching ``fleet.grid`` (``AlphaRR.fleet(fleet)``, ...).  For
        RR-style restrictions pass the restricted fleet
        (``fleet.restrict_to_endpoints()``), as with ``run_policy_batch``.
        Alternatively a SEQUENCE of policies — the fan-out axis: every
        entry (a ``PolicyFns``, or a ``policies.PolicyLane`` binding its
        own accounting grid + Model-2 ``svc_cols`` map) steps against the
        ONE shared obs stream inside the same compiled program, and the
        result comes back policy-major (``FleetResult.policy_view``) with
        lane p bitwise equal to its standalone run.  See "Policy fan-out"
        in the module docstring.
      fleet: the stacked instances (mixed horizons allowed).
      scenario: generate observations ON DEVICE inside the scan instead of
        reading them from ``fleet`` (which must then be obs-less:
        ``FleetBatch.for_scenario``).  Bit-identical to materializing the
        same scenario and running the classic path, with O(B * chunk)
        device memory and zero host->device observation transfer.
      mesh: 1-D device mesh with axis ``fleet`` (default: all devices).
      chunk_size: cut the horizon into chunks of this many slots (device-side
        outer scan).  None = one chunk.
      stream: drive the chunks from the host instead, one [B, chunk] slab at
        a time (requires ``chunk_size``); bit-identical to the scan driver.
        With a scenario the host ships only the scalar chunk offset.
      collect_trace: False drops the [B, T_max] ``r_hist`` output (the one
        O(T) device buffer) — totals/histograms are unchanged; use for
        T >= 10^6 horizons.
      n_seeds: run S Monte-Carlo replicas of every instance in the same
        compiled program (requires ``scenario=``): the seed is folded into
        every stream key *before* the per-slot counter fold
        (``scenarios.replicate_seeds``), so result row ``b * S + s`` is
        bit-identical to a standalone run of instance ``b`` under
        ``scenarios.with_seed(scenario, s)``.  The result carries
        ``n_seeds`` and a [B, S] ``seed_view``; collapse with
        ``mc_summary``.
      antithetic: pair the seed replicas (2m, 2m+1) antithetically on
        flip-capable streams (``scenarios.replicate_seeds(...,
        antithetic=True)``) — same estimator mean, tighter ``mc_summary``
        CIs on monotone statistics.  Requires an even ``n_seeds``.
      prng_backend: kernel backend for the scenario's counter-keyed
        uniforms ("xla" default — the canonical reference; "pallas" fuses
        the fold/salt/uniform chain via ``scenarios.with_prng_backend``).
        Bit-identical observations either way (requires ``scenario=``).
      with_opt_forward: co-execute the offline DP's [K] entry frontier per
        policy lane against the same shared stream (the cost-only forward
        pass — ``dp_fwd_chunk``, the offline drivers' own chunk kernel)
        and return ``FleetResult.opt_cost``: per row, bitwise the
        ``offline_opt_fleet(..., checkpointed=True,
        collect_schedule=False).cost`` of the lane's fleet.  A plain
        ``PolicyFns`` policy is treated as a single-lane fan-out.
      dp_backend: min-plus engine for the co-executed DP ("xla" default /
        "pallas"), exactly as in ``offline_opt_fleet``; only consulted
        with ``with_opt_forward=True``.
      async_ingest: with ``stream=True`` on an obs-backed fleet, prepare
        slab n+1 (host slicing + device put) on a background prefetch
        thread while the device executes slab n
        (``core.ingest.SlabPrefetcher``) — bit-identical to the
        synchronous loop, host work overlapped instead of serialized.
        A no-op with ``scenario=`` (fused generation ships no slabs).
      gather: on a process-spanning mesh, allgather the result rows so
        every process sees the full [B_global] fleet (one cross-host
        collective per result array).  Default False: results are this
        process's own rows, matching the local inputs.  A no-op on
        single-process meshes.  See "Multi-host fleets" above.

    Every configuration (any mesh size x any chunking x any driver x fused
    or materialized generation — and any ``prng_backend``) returns
    bit-identical results; see tests/test_fleet_engine.py,
    tests/test_scenarios.py, tests/test_mc_driver.py,
    tests/test_backend_dispatch.py and tests/test_policy_fanout.py.
    """
    lanes = as_policy_lanes(policy)
    if lanes is None and with_opt_forward:
        lanes = (PolicyLane(policy),)
    if lanes is not None:
        return _run_fleet_fanout(
            lanes, fleet, scenario=scenario, mesh=mesh,
            chunk_size=chunk_size, include_final_fetch=include_final_fetch,
            stream=stream, collect_trace=collect_trace, n_seeds=n_seeds,
            antithetic=antithetic, prng_backend=prng_backend,
            dp_backend=dp_backend, with_opt=with_opt_forward,
            async_ingest=async_ingest, gather=gather)
    if stream and chunk_size is None:
        raise ValueError("stream=True requires chunk_size")
    if async_ingest and not stream:
        raise ValueError("async_ingest=True requires stream=True (only the "
                         "host-driven driver ships slabs to prefetch)")
    _check_backends(dp_backend, prng_backend, scenario)
    fleet, scenario, S = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    if scenario is not None:
        scenario = with_prng_backend(scenario, prng_backend)
    policy = _replicate_policy(policy, S)
    B, T_max = fleet.B, fleet.T_max
    mesh, padded, n_chunks, T_pad = _prepare_fleet(fleet, mesh, chunk_size)
    params, lv, g, M = _policy_arrays(policy, padded, padded.B, mesh)

    if scenario is not None:
        _check_scenario(scenario, fleet)
        sparams = _dev_tree(mesh, _pad_params(scenario.params, padded.B))
        if stream:
            res = _run_fleet_scenario_streamed(
                policy, scenario, padded, params, sparams, lv, g, M, mesh,
                n_chunks, T_pad, include_final_fetch, collect_trace,
                B, T_max, fleet.T, S)
            return _gather_result(res, mesh) if gather else res
        core = _compiled_scenario_core(policy.init_fn, policy.step_fn,
                                       scenario.init_fn, scenario.chunk_fn,
                                       include_final_fetch, n_chunks,
                                       collect_trace, mesh)
        tids_all = _dev_replicated(mesh, np.arange(T_pad, dtype=np.int32))
        out = _dispatch(mesh, core, params, sparams, lv, g, M,
                        _dev_rows(mesh, padded.T), tids_all)
        r_hist, sums, counts = out if collect_trace else (None,) + out
        with _span("fleet.readback"):
            res = _fleet_result(r_hist, sums, counts, B, T_max, fleet.T, S)
        return _gather_result(res, mesh) if gather else res

    has_svc, has_side = fleet.svc is not None, fleet.side is not None
    if stream:
        res = _run_fleet_streamed(policy, padded, params, lv, g, M, mesh,
                                  n_chunks, include_final_fetch,
                                  collect_trace, B, T_max, fleet.T,
                                  async_ingest)
        return _gather_result(res, mesh) if gather else res

    core = _compiled_fleet_core(policy.init_fn, policy.step_fn,
                                include_final_fetch, n_chunks, has_svc,
                                has_side, collect_trace, mesh)
    args = (params, lv, g, M, _dev_rows(mesh, padded.T),
            _dev_rows(mesh, padded.x), _dev_rows(mesh, padded.c))
    if has_svc:
        args += (_dev_rows(mesh, padded.svc),)
    if has_side:
        args += (_dev_rows(mesh, padded.side),)
    out = _dispatch(mesh, core, *args)
    r_hist, sums, counts = out if collect_trace else (None,) + out
    with _span("fleet.readback"):
        res = _fleet_result(r_hist, sums, counts, B, T_max, fleet.T)
    return _gather_result(res, mesh) if gather else res


def _run_fleet_fanout(lanes, fleet: FleetBatch, *, scenario, mesh,
                      chunk_size, include_final_fetch, stream, collect_trace,
                      n_seeds, antithetic, prng_backend, dp_backend,
                      with_opt, async_ingest, gather) -> FleetResult:
    """Driver of the policy fan-out axis (see the module docstring): ONE
    generation pass, P policy lanes (+ optional per-lane DP frontiers),
    chunked or streamed, returning a policy-major ``FleetResult``."""
    if stream and chunk_size is None:
        raise ValueError("stream=True requires chunk_size")
    if async_ingest and not stream:
        raise ValueError("async_ingest=True requires stream=True (only the "
                         "host-driven driver ships slabs to prefetch)")
    _check_backends(dp_backend, prng_backend, scenario)
    has_svc = None if scenario is not None else fleet.svc is not None
    _check_lanes(lanes, fleet, has_svc)
    fleet, scenario, S = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    if scenario is not None:
        scenario = with_prng_backend(scenario, prng_backend)
    B, T_max = fleet.B, fleet.T_max
    mesh, padded, n_chunks, T_pad = _prepare_fleet(fleet, mesh, chunk_size)
    lane_args = _lane_arrays(lanes, padded, S, mesh)
    lane_fns = tuple((l.fns.init_fn, l.fns.step_fn) for l in lanes)
    lane_own = tuple(l.grid is not None for l in lanes)
    n_lanes = len(lanes)

    if scenario is not None:
        _check_scenario(scenario, fleet)
        sparams = _dev_tree(mesh, _pad_params(scenario.params, padded.B))
        if stream:
            return _run_fleet_fanout_streamed(
                lanes, lane_fns, lane_own, lane_args, scenario, padded,
                sparams, mesh, n_chunks, T_pad, include_final_fetch,
                collect_trace, with_opt, dp_backend, B, T_max, fleet.T, S,
                False, gather)
        core = _compiled_fanout_scenario_core(
            lane_fns, lane_own, scenario.init_fn, scenario.chunk_fn,
            include_final_fetch, n_chunks, collect_trace, with_opt,
            dp_backend, mesh)
        tids_all = _dev_replicated(mesh, np.arange(T_pad, dtype=np.int32))
        outs = _dispatch(mesh, core, lane_args, sparams,
                         _dev_rows(mesh, padded.T), tids_all)
    else:
        has_side = padded.side is not None
        if stream:
            return _run_fleet_fanout_streamed(
                lanes, lane_fns, lane_own, lane_args, None, padded, None,
                mesh, n_chunks, T_pad, include_final_fetch, collect_trace,
                with_opt, dp_backend, B, T_max, fleet.T, S, async_ingest,
                gather)
        core = _compiled_fanout_core(
            lane_fns, lane_own, include_final_fetch, n_chunks, has_svc,
            has_side, collect_trace, with_opt, dp_backend, mesh)
        args = (lane_args, _dev_rows(mesh, padded.T),
                _dev_rows(mesh, padded.x), _dev_rows(mesh, padded.c))
        if has_svc:
            args += (_dev_rows(mesh, padded.svc),)
        if has_side:
            args += (_dev_rows(mesh, padded.side),)
        outs = _dispatch(mesh, core, *args)
    i = 0
    r_lanes = None
    if collect_trace:
        r_lanes, i = outs[:n_lanes], n_lanes
    sums_lanes = outs[i:i + n_lanes]
    counts_lanes = outs[i + n_lanes:i + 2 * n_lanes]
    opt_lanes = outs[i + 2 * n_lanes:] if with_opt else None
    with _span("fleet.readback"):
        return _fanout_result(r_lanes, sums_lanes, counts_lanes, opt_lanes,
                              B, T_max, fleet.T, S, mesh, gather)


def _run_fleet_fanout_streamed(lanes, lane_fns, lane_own, lane_args,
                               scenario, padded, sparams, mesh, n_chunks,
                               T_pad, include_final_fetch, collect_trace,
                               with_opt, dp_backend, B, T_max, T_orig,
                               n_seeds, async_ingest, gather) -> FleetResult:
    """Host-driven fan-out streaming: a thin loop over the persistent
    fan-out ``FleetStepper`` (same donated-carry, zero-retrace contract as
    the single-policy streamed drivers)."""
    chunk = T_pad // n_chunks
    has_svc = scenario is None and padded.svc is not None
    has_side = scenario is None and padded.side is not None
    stepper = _make_fanout_stepper(lanes, lane_fns, lane_own, lane_args,
                                   scenario, padded, sparams, mesh, chunk,
                                   include_final_fetch, collect_trace,
                                   with_opt, dp_backend, True, has_svc,
                                   has_side, B, T_max, T_orig, n_seeds)
    if scenario is None:
        make_slab = _obs_slab_builder(padded, chunk, mesh, with_side=True)
        feed = slab_feed(make_slab, n_chunks, async_ingest)
    else:
        feed = (() for _ in range(n_chunks))
    r_parts = [[] for _ in lanes]
    for slabs in feed:
        rs = stepper.step_slabs(slabs)
        if collect_trace:
            for p, r in enumerate(rs):
                r_parts[p].append(_local_rows(r))
    r_hist = (tuple(np.concatenate(parts, axis=1) for parts in r_parts)
              if collect_trace else None)
    return stepper.result(r_hist, gather=gather)


def _sim_carry0(policy, params, B_pad, K, dt, mesh):
    return (_vmap_init(policy.init_fn, params, mesh),
            {"sums": _dev_rows(mesh, np.zeros((B_pad, 3), dt)),
             "counts": _dev_rows(mesh, np.zeros((B_pad, K), np.int32))})


# ----------------------------------------------------------------------
# FleetStepper: the one persistent slab-step implementation behind every
# streamed driver and the live-serving API.
# ----------------------------------------------------------------------

class FleetStepper:
    """Persistent, pre-compiled, donated-carry fleet stepper.

    ONE slab-step implementation behind three drivers
    (``_run_fleet_streamed``, ``_run_fleet_scenario_streamed``, the
    ``_dp_ckpt_streamed`` forward pass) and the public live-serving API
    (``fleet_stepper`` / ``serve.scheduler.LiveFleetScheduler``).  Holds a
    compiled step looked up from the module-level lru-cached factories
    (construction of a warm config never retraces), the device-resident
    carry, and the running slot offset; ``step_slabs`` advances the whole
    fleet one [B, chunk] slab and — with ``donate=True`` — hands the old
    carry and slab buffers back to XLA, so N steps allocate O(1) carries.

    Zero-recompile contract: the compiled step is a pure function of
    ``(policy/scenario fns, flags, mesh, donate)``, all shapes are fixed
    at construction, and ``T_len``/``t0`` are *traced* inputs — stepping
    past any horizon, or constructing a second stepper on the same
    config, triggers no new trace (``STREAM_TRACES`` is the test hook).

    Donation contract: after ``step_slabs`` returns, the previous carry
    and the slabs passed in are invalidated — callers must not retain
    references to them.  Paths that must (DP checkpoint collection) build
    their stepper with ``donate=False``.
    """

    def __init__(self, *, call, carry, chunk, mesh, has_out, kind,
                 scenario_mode, donate, B, B_pad, K, T_max, T_orig,
                 n_seeds=1, lv_host=None, with_svc=False, with_side=False,
                 fanout=False, n_policies=1, with_opt=False,
                 lane_lv_host=None):
        self._call = call
        # the step returns its carry row-sharded over the mesh; commit the
        # initial carry the same way, or the first step's uncommitted
        # input would give it a trace of its own
        self.carry = jax.device_put(carry, NamedSharding(mesh, P(FLEET_AXIS)))
        self.chunk = int(chunk)
        self._mesh = mesh
        self._has_out = has_out
        self._kind = kind                  # "sim" | "dp"
        self._scenario_mode = scenario_mode
        self.donate = donate
        self._B, self._B_pad, self._K = int(B), int(B_pad), int(K)
        self._T_max, self._T_orig = T_max, T_orig
        self._n_seeds = n_seeds
        self._lv_host = lv_host            # np [B_pad, K] level values
        self._with_svc, self._with_side = with_svc, with_side
        self._fanout = fanout              # multi-lane carry layout
        self.n_policies = int(n_policies)
        self._with_opt = with_opt          # co-executed DP frontiers
        self._lane_lv_host = lane_lv_host  # per-lane np [B_pad, K_p] levels
        self.t = 0                         # next slot offset
        self.steps = 0

    # ---- the one step ------------------------------------------------
    def step_slabs(self, slabs=()):
        """Advance one chunk on already-device-ready slab arrays (empty
        tuple for scenario-fused steppers).  Returns the step's [B_pad,
        chunk] output (hosting levels) or None for output-less steps."""
        # an uncommitted host scalar: valid as a replicated (P()) input on
        # both single- and multi-process meshes, identical trace either way
        t0 = np.int32(self.t)
        with _span("step.dispatch", slot=self.t), \
                shard_ctx(self._mesh, (FLEET_AXIS,), model_axis=None):
            out = self._call(self.carry, t0, tuple(slabs))
        if self._has_out:
            self.carry, y = out
        else:
            self.carry, y = out, None
        self.t += self.chunk
        self.steps += 1
        return y

    # ---- live telemetry admission (public sim steppers) --------------
    def _prep_slab(self, a, dtype, trailing=(), name="slab"):
        a = np.asarray(a)
        want = (self._B, self.chunk) + trailing
        if a.ndim == len(want) - 1 and self.chunk == 1:
            a = np.expand_dims(a, 1)                 # [B] -> [B, 1]
        if a.shape != want:
            raise ValueError(f"{name}: expected shape {want}, got {a.shape}")
        return _dev_rows(self._mesh, _pad_rows(a.astype(dtype, copy=False),
                                               self._B_pad, np))

    def step(self, x=None, c=None, svc=None, side=None):
        """Admit one chunk of live telemetry and advance the fleet.

        Obs-backed steppers take [B, chunk] ([B] when ``chunk == 1``)
        arrival counts ``x`` and rents ``c`` (plus [B, chunk, K] ``svc``
        and [B, chunk] ``side`` when constructed with those channels);
        scenario-fused steppers take no arguments (generation is on
        device).  Returns the [B, chunk] per-slot hosting levels when the
        stepper collects traces, else None.
        """
        if self._kind != "sim":
            raise ValueError("step() is for simulation steppers")
        slot = self.t                      # names this step's host spans
        if self._scenario_mode:
            if any(a is not None for a in (x, c, svc, side)):
                raise ValueError("scenario-fused stepper generates its own "
                                 "observations; step() takes no telemetry")
            out = self.step_slabs(())
        else:
            if x is None or c is None:
                raise ValueError("obs-backed stepper needs x= and c= slabs")
            dt = default_float_dtype()
            with _span("step.put", slot=slot):
                slabs = (self._prep_slab(x, np.int32, name="x"),
                         self._prep_slab(c, dt, name="c"))
                if self._with_svc:
                    slabs += (self._prep_slab(svc, dt, (self._K,),
                                              name="svc"),)
                elif svc is not None:
                    raise ValueError("stepper built without a svc channel")
                if self._with_side:
                    slabs += (self._prep_slab(side, np.int32, name="side"),)
                elif side is not None:
                    raise ValueError("stepper built without a side channel")
            out = self.step_slabs(slabs)
        if out is None:
            return None
        # blocks until the device has finished the step
        with _span("step.readback", slot=slot):
            if self._fanout:
                # one [B, chunk] level block per lane, stacked policy-major
                return np.stack([_local_rows(r)[:self._B] for r in out])
            return _local_rows(out)[:self._B]

    # ---- readbacks ---------------------------------------------------
    # On a process-spanning mesh every readback is this process's own
    # [B_local] rows (matching the local telemetry it admits); pass
    # ``gather=True`` for the full [B_global] fleet view (one cross-host
    # collective).  ``gather`` is a no-op on single-process meshes.

    def _lane_sims(self):
        """The tuple of per-lane (state, acc) carries (fan-out steppers)."""
        return self.carry[1] if self._scenario_mode else self.carry[0]

    def _lane_Js(self):
        """The tuple of per-lane DP frontiers (with_opt fan-out steppers)."""
        if not self._with_opt:
            raise ValueError("opt readback needs with_opt_forward=True")
        return self.carry[2] if self._scenario_mode else self.carry[1]

    def _sim_carry(self, policy: int = 0):
        if self._kind != "sim":
            raise ValueError("simulation readback on a DP stepper")
        if self._fanout:
            return self._lane_sims()[policy]
        if policy:
            raise ValueError("policy= readback needs a fan-out stepper")
        return self.carry[1] if self._scenario_mode else self.carry

    def hosting_levels(self, gather: bool = False,
                       policy: int = 0) -> np.ndarray:
        """[B] current per-instance hosting level *indices* r_t (of fan-out
        lane ``policy``, on multi-policy steppers)."""
        state, _ = self._sim_carry(policy)
        r = _local_rows(state["r"])[:self._B].astype(np.int64)
        return _gather_rows(self._mesh, r) if gather else r

    def hosting_fractions(self, gather: bool = False,
                          policy: int = 0) -> np.ndarray:
        """[B] current per-instance hosting *fractions* (the level values
        ell_{r_t} in [0, 1]) — the live serving decision readback."""
        r = self.hosting_levels(policy=policy)
        lv = (self._lane_lv_host[policy] if self._fanout
              else self._lv_host)[:self._B]
        frac = np.take_along_axis(lv, r[:, None], axis=1)[:, 0]
        return _gather_rows(self._mesh, frac) if gather else frac

    def opt_cost(self, gather: bool = False,
                 policy: Optional[int] = None) -> np.ndarray:
        """Current offline-DP optimum of the slots stepped so far, from the
        co-executed frontiers (``with_opt_forward=True`` steppers): the
        host-side ``J.min(axis=1)`` every streamed DP driver uses.  [B] for
        one ``policy=`` lane, else [P, B] over all lanes."""
        Js = self._lane_Js()
        if policy is not None:
            Js = (Js[policy],)
        gr = ((lambda a: _gather_rows(self._mesh, a)) if gather
              else (lambda a: a))
        costs = [gr(_local_rows(J)[:self._B].min(axis=1).astype(np.float64))
                 for J in Js]
        return costs[0] if policy is not None else np.stack(costs)

    def frontier(self, gather: bool = False) -> np.ndarray:
        """[B, K] DP value frontier (DP steppers only)."""
        if self._kind != "dp":
            raise ValueError("frontier() is for DP steppers")
        J = self.carry[1] if self._scenario_mode else self.carry
        J = _local_rows(J)[:self._B]
        return _gather_rows(self._mesh, J) if gather else J

    def result(self, r_hist=None, gather: bool = False) -> FleetResult:
        """Totals accumulated so far as a ``FleetResult`` (bit-identical
        to one ``run_fleet`` call over the same slabs — the engine
        invariant).  ``r_hist``: optionally, the concatenated per-step
        level outputs to attach as the trace (on a fan-out stepper, a
        per-lane tuple — the result is policy-major, with ``opt_cost``
        attached when constructed with ``with_opt_forward=True``)."""
        if self._fanout:
            if self._kind != "sim":
                raise ValueError("simulation readback on a DP stepper")
            sims = self._lane_sims()
            opt_lanes = None
            if self._with_opt:
                opt_lanes = tuple(
                    _local_rows(J)[:self._B].min(axis=1)
                    for J in self._lane_Js())
            return _fanout_result(
                r_hist, tuple(acc["sums"] for (_, acc) in sims),
                tuple(acc["counts"] for (_, acc) in sims), opt_lanes,
                self._B, self._T_max, self._T_orig, self._n_seeds,
                self._mesh, gather)
        (_, acc) = self._sim_carry()
        res = _fleet_result(r_hist, acc["sums"], acc["counts"], self._B,
                            self._T_max, self._T_orig, self._n_seeds)
        return _gather_result(res, self._mesh) if gather else res


def _obs_slab_builder(padded: FleetBatch, chunk: int, mesh, with_side: bool):
    """make_slab(i) for obs-backed streaming: slice host-resident numpy
    obs and device-put one [B, chunk] slab — the unit of work
    ``SlabPrefetcher`` overlaps with device compute.  On a process-spanning
    mesh each process holds (and ships) only its own [B_local, chunk] rows;
    ``_dev_rows`` assembles the global slab from them with zero cross-host
    observation bytes (metadata-only assembly, safe on the prefetch
    thread)."""
    x_h, c_h = np.asarray(padded.x), np.asarray(padded.c)
    svc_h = None if padded.svc is None else np.asarray(padded.svc)
    side_h = (None if not with_side or padded.side is None
              else np.asarray(padded.side))

    def make_slab(i):
        sl = slice(i * chunk, (i + 1) * chunk)
        slabs = (_dev_rows(mesh, x_h[:, sl]), _dev_rows(mesh, c_h[:, sl]))
        if svc_h is not None:
            slabs += (_dev_rows(mesh, svc_h[:, sl]),)
        if side_h is not None:
            slabs += (_dev_rows(mesh, side_h[:, sl]),)
        return slabs

    return make_slab


def _make_sim_stepper(policy, scenario, padded, params, sparams, lv, g, M,
                      mesh, chunk, include_final_fetch, collect_trace,
                      donate, has_svc, has_side, B, T_max, T_orig, n_seeds):
    """Build a simulation ``FleetStepper`` (obs-backed or scenario-fused)
    from an already-padded fleet: looks up the compiled step, builds the
    initial carry, closes over the resident arrays."""
    T_dev = _dev_rows(mesh, padded.T)
    if scenario is not None:
        step = _compiled_scenario_stream_step(
            policy.init_fn, policy.step_fn, scenario.init_fn,
            scenario.chunk_fn, include_final_fetch, chunk, collect_trace,
            mesh, donate)
        carry = (_vmap_init(scenario.init_fn, sparams, mesh),
                 _sim_carry0(policy, params, padded.B, padded.K, lv.dtype,
                             mesh))

        def call(carry, t0, slabs):
            return step(params, sparams, lv, g, M, T_dev, t0, carry)

        has_out = collect_trace
    else:
        step = _compiled_stream_step(policy.init_fn, policy.step_fn,
                                     include_final_fetch, has_svc, has_side,
                                     mesh, donate)
        carry = _sim_carry0(policy, params, padded.B, padded.K, lv.dtype,
                            mesh)

        def call(carry, t0, slabs):
            return step(params, lv, g, M, T_dev, t0, carry, *slabs)

        has_out = True
    return FleetStepper(call=call, carry=carry, chunk=chunk, mesh=mesh,
                        has_out=has_out, kind="sim",
                        scenario_mode=scenario is not None, donate=donate,
                        B=B, B_pad=padded.B, K=padded.K, T_max=T_max,
                        T_orig=T_orig, n_seeds=n_seeds,
                        lv_host=_local_rows(lv), with_svc=has_svc,
                        with_side=has_side)


def _make_fanout_stepper(lanes, lane_fns, lane_own, lane_args, scenario,
                         padded, sparams, mesh, chunk, include_final_fetch,
                         collect_trace, with_opt, dp_backend, donate,
                         has_svc, has_side, B, T_max, T_orig, n_seeds):
    """Build a fan-out ``FleetStepper``: the compiled multi-lane slab step,
    the tuple-of-lane-carries (+ per-lane DP frontiers with ``with_opt``),
    per-lane level rows for the fraction readbacks."""
    T_dev = _dev_rows(mesh, padded.T)
    dt = default_float_dtype()
    B_pad = padded.B
    sims0 = tuple(
        (_vmap_init(fns[0], largs[0], mesh),
         {"sums": _dev_rows(mesh, np.zeros((B_pad, 3), dt)),
          "counts": _dev_rows(mesh, np.zeros((B_pad, largs[1].shape[-1]),
                                             np.int32))})
        for fns, largs in zip(lane_fns, lane_args))
    Js0 = ()
    if with_opt:
        Js0 = (tuple(
            _dev_rows(mesh, np.broadcast_to(
                np.asarray(dp_frontier0(largs[1].shape[-1])),
                (B_pad, largs[1].shape[-1])))
            for largs in lane_args),)
    if scenario is not None:
        step = _compiled_fanout_scenario_stream_step(
            lane_fns, lane_own, scenario.init_fn, scenario.chunk_fn,
            include_final_fetch, chunk, collect_trace, with_opt, dp_backend,
            mesh, donate)
        carry = (_vmap_init(scenario.init_fn, sparams, mesh), sims0) + Js0

        def call(carry, t0, slabs):
            return step(lane_args, sparams, T_dev, t0, carry)
    else:
        step = _compiled_fanout_stream_step(
            lane_fns, lane_own, include_final_fetch, has_svc, has_side,
            collect_trace, with_opt, dp_backend, mesh, donate)
        carry = (sims0,) + Js0

        def call(carry, t0, slabs):
            return step(lane_args, T_dev, t0, carry, *slabs)

    return FleetStepper(
        call=call, carry=carry, chunk=chunk, mesh=mesh,
        has_out=collect_trace, kind="sim",
        scenario_mode=scenario is not None, donate=donate, B=B, B_pad=B_pad,
        K=padded.K, T_max=T_max, T_orig=T_orig, n_seeds=n_seeds,
        lv_host=_local_rows(lane_args[0][1]), with_svc=has_svc,
        with_side=has_side, fanout=True, n_policies=len(lanes),
        with_opt=with_opt,
        lane_lv_host=tuple(_local_rows(a[1]) for a in lane_args))


def fleet_stepper(policy, fleet: FleetBatch, *,
                  scenario: Optional[Scenario] = None,
                  mesh: Optional[Mesh] = None, chunk_size: int = 1,
                  include_final_fetch: bool = True,
                  collect_trace: bool = True,
                  n_seeds: Optional[int] = None, antithetic: bool = False,
                  prng_backend: str = "xla",
                  with_opt_forward: bool = False,
                  dp_backend: str = "xla",
                  donate: bool = True) -> FleetStepper:
    """Long-lived stepping API for live fleets: pre-compile once, then
    ``step()`` the whole fleet one [B, chunk_size] telemetry slab at a
    time with zero retraces and a donated carry.

    ``policy`` may be a SEQUENCE of policies (``PolicyFns`` /
    ``PolicyLane`` lanes, as in ``run_fleet``): every admitted slab then
    steps all lanes in one compiled call — the live scheduler's
    shadow-scoring hook (``LiveFleetScheduler``), where candidate policies
    accumulate their would-have-been costs on the production telemetry.
    Readbacks take ``policy=`` lane indices; ``step()`` returns [P, B,
    chunk] levels; ``result()`` is policy-major.  ``with_opt_forward=True``
    co-advances each lane's offline-DP frontier (``opt_cost()`` readback —
    the exact hindsight optimum of the slots admitted so far).

    Obs-backed mode (``scenario=None``): telemetry arrives through
    ``step(x, c[, svc][, side])`` — the fleet only contributes its grid
    and per-instance horizons (``FleetBatch.for_scenario`` is the natural
    constructor; a fleet's materialized obs are NOT consumed here).  For
    an open-ended live fleet, construct with a generous horizon ``T`` —
    the horizon mask is a traced input, so it costs nothing, and slots
    past each instance's own T_i stay exact no-ops.

    Scenario-fused mode: ``step()`` takes no arguments; the generator
    advances on device (``n_seeds``/``antithetic``/``prng_backend``
    compose exactly as in ``run_fleet``).

    N ``step()`` calls are bit-identical to one ``run_fleet`` call over
    the same observations — the engine invariant, proven in
    tests/test_fleet_stepper.py across chunked/streamed x obs/scenario x
    ``n_seeds`` x device-count configs.  ``donate=False`` only if you
    must retain carry references across steps.
    """
    lanes = as_policy_lanes(policy)
    if lanes is None and with_opt_forward:
        lanes = (PolicyLane(policy),)
    _check_backends(dp_backend, prng_backend, scenario)
    if scenario is None and n_seeds is not None:
        raise ValueError("n_seeds= needs scenario= (as in run_fleet)")
    if lanes is not None:
        _check_lanes(lanes, fleet,
                     None if scenario is not None else fleet.svc is not None)
    fleet, scenario, S = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    if scenario is not None:
        _check_scenario(scenario, fleet)
        scenario = with_prng_backend(scenario, prng_backend)
    B, T_max = fleet.B, fleet.T_max
    mesh, padded, _, _ = _prepare_fleet(fleet, mesh, int(chunk_size))
    sparams = (None if scenario is None
               else _dev_tree(mesh, _pad_params(scenario.params, padded.B)))
    has_svc = scenario is None and fleet.svc is not None
    has_side = scenario is None and fleet.side is not None
    if lanes is not None:
        lane_args = _lane_arrays(lanes, padded, S, mesh)
        lane_fns = tuple((l.fns.init_fn, l.fns.step_fn) for l in lanes)
        lane_own = tuple(l.grid is not None for l in lanes)
        return _make_fanout_stepper(lanes, lane_fns, lane_own, lane_args,
                                    scenario, padded, sparams, mesh,
                                    int(chunk_size), include_final_fetch,
                                    collect_trace, with_opt_forward,
                                    dp_backend, donate, has_svc, has_side,
                                    B, T_max, fleet.T, S)
    policy = _replicate_policy(policy, S)
    params, lv, g, M = _policy_arrays(policy, padded, padded.B, mesh)
    return _make_sim_stepper(policy, scenario, padded, params, sparams, lv,
                             g, M, mesh, int(chunk_size),
                             include_final_fetch, collect_trace, donate,
                             has_svc, has_side, B, T_max, fleet.T, S)


def _run_fleet_streamed(policy, padded, params, lv, g, M, mesh, n_chunks,
                        include_final_fetch, collect_trace, B, T_max, T_orig,
                        async_ingest=False):
    """Host-driven streaming: numpy slabs in, carry stays on device — a
    thin loop over the persistent ``FleetStepper`` (donated carry, zero
    retraces after warmup; ``async_ingest=True`` prefetches slab n+1 on a
    background thread while the device executes slab n)."""
    has_svc, has_side = padded.svc is not None, padded.side is not None
    chunk = padded.T_max // n_chunks
    stepper = _make_sim_stepper(policy, None, padded, params, None, lv, g, M,
                                mesh, chunk, include_final_fetch,
                                collect_trace, True, has_svc, has_side,
                                B, T_max, T_orig, 1)
    make_slab = _obs_slab_builder(padded, chunk, mesh, with_side=True)
    r_parts = []
    for slabs in slab_feed(make_slab, n_chunks, async_ingest):
        r_chunk = stepper.step_slabs(slabs)
        if collect_trace:
            r_parts.append(_local_rows(r_chunk))
    r_hist = np.concatenate(r_parts, axis=1) if collect_trace else None
    return stepper.result(r_hist)


def _run_fleet_scenario_streamed(policy, scenario, padded, params, sparams,
                                 lv, g, M, mesh, n_chunks, T_pad,
                                 include_final_fetch, collect_trace,
                                 B, T_max, T_orig, n_seeds=1):
    """Host-driven streaming with fused generation: per chunk the host
    ships ONE scalar (the chunk offset); obs never exist on the host.  A
    thin loop over the persistent ``FleetStepper``."""
    chunk = T_pad // n_chunks
    stepper = _make_sim_stepper(policy, scenario, padded, params, sparams,
                                lv, g, M, mesh, chunk, include_final_fetch,
                                collect_trace, True, False, False,
                                B, T_max, T_orig, n_seeds)
    r_parts = []
    for _ in range(n_chunks):
        r_chunk = stepper.step_slabs(())
        if collect_trace:
            r_parts.append(_local_rows(r_chunk))
    r_hist = np.concatenate(r_parts, axis=1) if collect_trace else None
    return stepper.result(r_hist)


# ----------------------------------------------------------------------
# Offline DP on a fleet: chunked forward recursion, frozen past T_i.
# The chunk-level recursion itself (``dp_fwd_chunk`` / ``dp_backtrack*``)
# lives in ``policies.offline_opt`` — ONE copy shared by every driver here.
# ``dp_backend`` threads through every core factory into that one call
# site (and into the compile-cache keys, so backends never share a trace).
# ----------------------------------------------------------------------

def _check_backends(dp_backend: str, prng_backend: str,
                    scenario=None) -> None:
    """Validate the engine entry points' backend arguments up front."""
    if dp_backend not in DP_BACKENDS:
        raise ValueError(f"dp_backend must be one of {DP_BACKENDS}, "
                         f"got {dp_backend!r}")
    if prng_backend not in PRNG_BACKENDS:
        raise ValueError(f"prng_backend must be one of {PRNG_BACKENDS}, "
                         f"got {prng_backend!r}")
    if prng_backend != "xla" and scenario is None:
        raise ValueError("prng_backend= needs scenario=: materialized "
                         "observations draw no slot uniforms to reroute")


def _make_dp_instance_core(n_chunks: int, has_svc: bool,
                           dp_backend: str = "xla"):
    """Forward DP + reverse backtrack for ONE instance, chunk-capable.

    Matches ``offline_opt._dp_core`` op-for-op on valid slots; invalid slots
    (t >= T_len) keep ``J`` frozen and write identity backpointers, so the
    backtracked schedule is constant past T_len and the cost is exactly the
    instance's own-horizon optimum.  Padded K levels are priced ``+inf``
    exactly as in ``offline_opt_batch``.  This is the *materialized* path:
    the whole [T_pad, K] argmin table is kept for the backtrack (see
    ``_make_dp_ckpt_instance_core`` for the O(chunk * K) alternative).
    """

    def core(M, lv, g, kmask, T_len, x, c, *opt):
        K = lv.shape[-1]
        svc = opt[0] if has_svc else None
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)

        def fwd_chunk(J, t0, xck, cck, sck):
            if sck is None:
                sck = _model1_svc(xck, g)
            tids = t0 + jnp.arange(xck.shape[-1], dtype=jnp.int32)
            return dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat,
                                T_len, dp_backend)

        J_T, args = _chunked_drive(fwd_chunk, dp_frontier0(K), n_chunks,
                                   (x, c, svc))
        return dp_backtrack(J_T, args)

    return core


def _make_dp_scenario_core(sc_init, sc_chunk, n_chunks: int,
                           dp_backend: str = "xla"):
    """Scenario-fused forward DP for ONE instance: slabs are generated
    inside the chunk scan (generator state in the carry next to J); the
    recursion itself is ``dp_fwd_chunk``, shared with the obs-backed core."""

    def core(sparams, M, lv, g, kmask, T_len, tids_all):
        K = lv.shape[-1]
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)

        def fwd_chunk(carry, t0, tids):
            gen_state, J = carry
            gen_state, slab = sc_chunk(sparams, gen_state, tids)
            sck = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
            J, args = dp_fwd_chunk(J, tids, slab.c, sck, lv32, kmask,
                                   fetch_mat, T_len, dp_backend)
            return (gen_state, J), args

        carry0 = (sc_init(sparams), dp_frontier0(K))
        (_, J_T), args = _chunked_drive(fwd_chunk, carry0, n_chunks,
                                        (tids_all,))
        return dp_backtrack(J_T, args)

    return core


# ----------------------------------------------------------------------
# Checkpointed two-pass DP: forward stores one [K] frontier per chunk,
# backtrack replays chunks in reverse, recomputing argmins on the fly —
# no [T, K] (so no [B, T, K]) backpointer table ever exists.
# ----------------------------------------------------------------------

def _make_dp_ckpt_instance_core(n_chunks: int, has_svc: bool,
                                collect_schedule: bool,
                                dp_backend: str = "xla"):
    """Checkpointed DP for ONE instance, obs-backed.

    Pass 1 runs ``dp_fwd_chunk`` over the chunks, emitting each chunk's
    *entry* frontier (a [K] row) instead of its [chunk, K] argmin table;
    pass 2 scans the chunks in reverse, recomputing each table from its
    checkpoint with the *same* ``dp_fwd_chunk`` and backtracking through it
    (``dp_backtrack_chunk``), chaining ``k`` right-to-left.  The (k, arg)
    op sequence is identical to the materialized backtrack, so the result
    is bit-identical; peak memory drops from O(T * K) to
    O((chunk + n_chunks) * K) per instance.  ``collect_schedule=False``
    skips pass 2 entirely (cost only — nothing O(T) remains at all).
    """

    def core(M, lv, g, kmask, T_len, x, c, *opt):
        K = lv.shape[-1]
        svc = opt[0] if has_svc else None
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        T_pad = x.shape[0]
        chunk = T_pad // n_chunks
        cut = lambda a: (None if a is None
                         else a.reshape((n_chunks, chunk) + a.shape[1:]))
        xs, cs, ss = cut(x), cut(c), cut(svc)
        t0s = jnp.arange(n_chunks, dtype=jnp.int32) * chunk

        def chunk_pass(J, t0, xck, cck, sck):
            if sck is None:
                sck = _model1_svc(xck, g)
            tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
            return dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat,
                                T_len, dp_backend)

        def fwd(J, inp):
            t0, xck, cck, sck = inp
            J2, _ = chunk_pass(J, t0, xck, cck, sck)
            return J2, J                    # checkpoint = chunk-ENTRY frontier

        J_T, ckpts = jax.lax.scan(fwd, dp_frontier0(K), (t0s, xs, cs, ss))
        cost = jnp.min(J_T)
        if not collect_schedule:
            return cost
        k_T = jnp.argmin(J_T)

        def bwd(k, inp):
            Jck, t0, xck, cck, sck = inp
            _, args = chunk_pass(Jck, t0, xck, cck, sck)
            return dp_backtrack_chunk(k, args)

        _, r = jax.lax.scan(bwd, k_T, (ckpts, t0s, xs, cs, ss), reverse=True)
        return cost, r.reshape(T_pad).astype(jnp.int32)

    return core


def _make_dp_ckpt_scenario_core(sc_init, sc_chunk, n_chunks: int,
                                collect_schedule: bool,
                                dp_backend: str = "xla"):
    """Checkpointed DP with fused generation: pass 1 additionally
    checkpoints the generator state at each chunk entry (small — recursion
    state only, the innovations are counter-keyed), so pass 2 regenerates
    each chunk's slab from ``(gen checkpoint, tids)`` and recomputes its
    argmin table — the same counter-keyed regeneration trick the fused
    simulator uses, applied to the backtrack."""

    def core(sparams, M, lv, g, kmask, T_len, tids_all):
        K = lv.shape[-1]
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        T_pad = tids_all.shape[0]
        chunk = T_pad // n_chunks
        tcks = tids_all.reshape(n_chunks, chunk)

        def chunk_pass(J, gen_state, tids):
            gen2, slab = sc_chunk(sparams, gen_state, tids)
            sck = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
            J2, args = dp_fwd_chunk(J, tids, slab.c, sck, lv32, kmask,
                                    fetch_mat, T_len, dp_backend)
            return gen2, J2, args

        def fwd(carry, tids):
            gen_state, J = carry
            gen2, J2, _ = chunk_pass(J, gen_state, tids)
            return (gen2, J2), (gen_state, J)      # entry-state checkpoints

        carry0 = (sc_init(sparams), dp_frontier0(K))
        (_, J_T), (gen_ckpts, J_ckpts) = jax.lax.scan(fwd, carry0, tcks)
        cost = jnp.min(J_T)
        if not collect_schedule:
            return cost
        k_T = jnp.argmin(J_T)

        def bwd(k, inp):
            gen_ck, Jck, tids = inp
            _, _, args = chunk_pass(Jck, gen_ck, tids)
            return dp_backtrack_chunk(k, args)

        _, r = jax.lax.scan(bwd, k_T, (gen_ckpts, J_ckpts, tcks),
                            reverse=True)
        return cost, r.reshape(T_pad).astype(jnp.int32)

    return core


@functools.lru_cache(maxsize=32)
def _compiled_dp_core(n_chunks: int, has_svc: bool, mesh: Mesh,
                      dp_backend: str = "xla"):
    core = _make_dp_instance_core(n_chunks, has_svc, dp_backend)
    spec = P(FLEET_AXIS)
    sharded = _fleet_map(jax.vmap(core), mesh=mesh,
                         in_specs=(spec,) * (7 + int(has_svc)),
                         out_specs=(spec, spec))
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_dp_scenario_core(sc_init, sc_chunk, n_chunks: int, mesh: Mesh,
                               dp_backend: str = "xla"):
    core = _make_dp_scenario_core(sc_init, sc_chunk, n_chunks, dp_backend)
    spec = P(FLEET_AXIS)
    sharded = _fleet_map(jax.vmap(core, in_axes=(0, 0, 0, 0, 0, 0, None)),
                         mesh=mesh, in_specs=(spec,) * 6 + (P(),),
                         out_specs=(spec, spec))
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_dp_ckpt_core(n_chunks: int, has_svc: bool,
                           collect_schedule: bool, mesh: Mesh,
                           dp_backend: str = "xla"):
    core = _make_dp_ckpt_instance_core(n_chunks, has_svc, collect_schedule,
                                       dp_backend)
    spec = P(FLEET_AXIS)
    out_specs = (spec, spec) if collect_schedule else spec
    sharded = _fleet_map(jax.vmap(core), mesh=mesh,
                         in_specs=(spec,) * (7 + int(has_svc)),
                         out_specs=out_specs)
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_dp_ckpt_scenario_core(sc_init, sc_chunk, n_chunks: int,
                                    collect_schedule: bool, mesh: Mesh,
                                    dp_backend: str = "xla"):
    core = _make_dp_ckpt_scenario_core(sc_init, sc_chunk, n_chunks,
                                       collect_schedule, dp_backend)
    spec = P(FLEET_AXIS)
    out_specs = (spec, spec) if collect_schedule else spec
    sharded = _fleet_map(jax.vmap(core, in_axes=(0, 0, 0, 0, 0, 0, None)),
                         mesh=mesh, in_specs=(spec,) * 6 + (P(),),
                         out_specs=out_specs)
    return jax.jit(sharded)


# ---- streamed checkpointed drivers: the host drives the two passes one
# chunk at a time, so neither obs nor r_hist is ever device-resident whole.

@functools.lru_cache(maxsize=32)
def _compiled_dp_stream_fwd(has_svc: bool, mesh: Mesh,
                            dp_backend: str = "xla",
                            donate: bool = False):
    """One forward slab of the value recursion: ``J -> J'``.
    ``donate=True`` donates the frontier and slab buffers — only legal for
    cost-only solves (``collect_schedule=True`` retains old frontiers as
    backtrack checkpoints, so it must keep ``donate=False``)."""

    def step(M, lv, g, kmask, T_len, t0, J, xck, cck, *opt):
        STREAM_TRACES["dp_fwd_obs"] += 1
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        sck = opt[0] if has_svc else _model1_svc(xck, g)
        tids = t0 + jnp.arange(xck.shape[-1], dtype=jnp.int32)
        J2, _ = dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat,
                             T_len, dp_backend)
        return J2

    n_opt = int(has_svc)
    in_axes = (0, 0, 0, 0, 0, None, 0, 0, 0) + (0,) * n_opt
    spec = P(FLEET_AXIS)
    in_specs = (spec,) * 5 + (P(),) + (spec,) * (3 + n_opt)
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes), mesh=mesh,
                         in_specs=in_specs, out_specs=spec)
    donate_argnums = tuple(range(6, 9 + n_opt)) if donate else ()
    return jax.jit(sharded, donate_argnums=donate_argnums)


@functools.lru_cache(maxsize=32)
def _compiled_dp_stream_bwd(has_svc: bool, mesh: Mesh,
                            dp_backend: str = "xla"):
    """One backward slab: recompute the chunk's argmins from its checkpoint
    and backtrack through them — ``(J_ckpt, k) -> (k_entry, r_chunk)``."""

    def step(M, lv, g, kmask, T_len, t0, Jck, k, xck, cck, *opt):
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        sck = opt[0] if has_svc else _model1_svc(xck, g)
        tids = t0 + jnp.arange(xck.shape[-1], dtype=jnp.int32)
        _, args = dp_fwd_chunk(Jck, tids, cck, sck, lv32, kmask, fetch_mat,
                               T_len, dp_backend)
        k0, rck = dp_backtrack_chunk(k, args)
        return k0, rck.astype(jnp.int32)

    n_opt = int(has_svc)
    in_axes = (0, 0, 0, 0, 0, None, 0, 0, 0, 0) + (0,) * n_opt
    spec = P(FLEET_AXIS)
    in_specs = (spec,) * 5 + (P(),) + (spec,) * (4 + n_opt)
    sharded = _fleet_map(jax.vmap(step, in_axes=in_axes), mesh=mesh,
                         in_specs=in_specs, out_specs=(spec, spec))
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_dp_scenario_stream_fwd(sc_init, sc_chunk, chunk: int,
                                     mesh: Mesh, dp_backend: str = "xla",
                                     donate: bool = False):
    """One fused-generation forward slab: the host ships one scalar offset
    per chunk; ``(gen_state, J) -> (gen', J')``.  ``donate=True`` donates
    the carry — cost-only solves only (see ``_compiled_dp_stream_fwd``)."""

    def step(sparams, M, lv, g, kmask, T_len, t0, carry):
        STREAM_TRACES["dp_fwd_scenario"] += 1
        gen_state, J = carry
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
        gen2, slab = sc_chunk(sparams, gen_state, tids)
        sck = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
        J2, _ = dp_fwd_chunk(J, tids, slab.c, sck, lv32, kmask, fetch_mat,
                             T_len, dp_backend)
        return gen2, J2

    spec = P(FLEET_AXIS)
    sharded = _fleet_map(
        jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0, None, 0)), mesh=mesh,
        in_specs=(spec,) * 6 + (P(), spec), out_specs=(spec, spec))
    return jax.jit(sharded, donate_argnums=(7,) if donate else ())


@functools.lru_cache(maxsize=32)
def _compiled_dp_scenario_stream_bwd(sc_init, sc_chunk, chunk: int,
                                     mesh: Mesh, dp_backend: str = "xla"):
    """One fused-generation backward slab: regenerate the chunk from its
    generator-state checkpoint, recompute its argmins, backtrack."""

    def step(sparams, M, lv, g, kmask, T_len, t0, gen_ck, Jck, k):
        lv32 = lv.astype(jnp.float32)
        fetch_mat = dp_fetch_matrix(M.astype(jnp.float32), lv32)
        tids = t0 + jnp.arange(chunk, dtype=jnp.int32)
        _, slab = sc_chunk(sparams, gen_ck, tids)
        sck = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
        _, args = dp_fwd_chunk(Jck, tids, slab.c, sck, lv32, kmask, fetch_mat,
                               T_len, dp_backend)
        k0, rck = dp_backtrack_chunk(k, args)
        return k0, rck.astype(jnp.int32)

    spec = P(FLEET_AXIS)
    sharded = _fleet_map(
        jax.vmap(step, in_axes=(0, 0, 0, 0, 0, 0, None, 0, 0, 0)), mesh=mesh,
        in_specs=(spec,) * 6 + (P(),) + (spec,) * 3, out_specs=(spec, spec))
    return jax.jit(sharded)


def _dp_grid_args(padded: FleetBatch, mesh):
    dt = default_float_dtype()
    return (_dev_rows(mesh, padded.grid.M.astype(dt)),
            _dev_rows(mesh, padded.grid.levels.astype(dt)),
            _dev_rows(mesh, padded.grid.g.astype(dt)),
            _dev_rows(mesh, padded.grid.mask),
            _dev_rows(mesh, padded.T))


def _dp_scan_core_args(scenario, padded, mesh, n_chunks, T_pad,
                       checkpointed: bool, collect_schedule: bool,
                       dp_backend: str = "xla"):
    """(compiled device-scan DP core, its args) for this config — shared by
    ``offline_opt_fleet`` and ``offline_dp_memory_stats`` so the probed
    program is exactly the executed one."""
    grid_args = _dp_grid_args(padded, mesh)
    if scenario is not None:
        sparams = _dev_tree(mesh, _pad_params(scenario.params, padded.B))
        if checkpointed:
            core = _compiled_dp_ckpt_scenario_core(
                scenario.init_fn, scenario.chunk_fn, n_chunks,
                collect_schedule, mesh, dp_backend)
        else:
            core = _compiled_dp_scenario_core(scenario.init_fn,
                                              scenario.chunk_fn, n_chunks,
                                              mesh, dp_backend)
        args = (sparams,) + grid_args + (
            _dev_replicated(mesh, np.arange(T_pad, dtype=np.int32)),)
    else:
        has_svc = padded.svc is not None
        if checkpointed:
            core = _compiled_dp_ckpt_core(n_chunks, has_svc, collect_schedule,
                                          mesh, dp_backend)
        else:
            core = _compiled_dp_core(n_chunks, has_svc, mesh, dp_backend)
        args = grid_args + (_dev_rows(mesh, padded.x),
                            _dev_rows(mesh, padded.c))
        if has_svc:
            args += (_dev_rows(mesh, padded.svc),)
    return core, args


def _dp_ckpt_streamed(scenario, padded, mesh, n_chunks, T_pad,
                      collect_schedule: bool, dp_backend: str = "xla",
                      async_ingest: bool = False):
    """Host-driven checkpointed DP: forward loop collecting per-chunk
    frontier (+ generator-state) checkpoints in a device-resident list,
    then a backward loop replaying the chunks in reverse.  With a scenario
    the host ships one scalar offset per chunk each way; obs-backed fleets
    slab-feed host-resident numpy arrays like ``_run_fleet_streamed``
    (``async_ingest=True`` prefetches the slabs of BOTH passes).

    The forward pass is a thin loop over the persistent ``FleetStepper``.
    Donation rule: cost-only solves donate the frontier carry; with
    ``collect_schedule=True`` the old carries ARE the backtrack
    checkpoints, so that path must run ``donate=False``.
    """
    chunk = T_pad // n_chunks
    grid_args = _dp_grid_args(padded, mesh)
    B_pad, K = padded.B, padded.K
    T_orig = None      # stepper result metadata, unused by DP readbacks
    donate = not collect_schedule
    J0 = _dev_rows(mesh, np.broadcast_to(np.asarray(dp_frontier0(K)),
                                         (B_pad, K)))
    if scenario is not None:
        sparams = _dev_tree(mesh, _pad_params(scenario.params, padded.B))
        fwd = _compiled_dp_scenario_stream_fwd(scenario.init_fn,
                                               scenario.chunk_fn, chunk,
                                               mesh, dp_backend, donate)
        bwd = _compiled_dp_scenario_stream_bwd(scenario.init_fn,
                                               scenario.chunk_fn, chunk,
                                               mesh, dp_backend)
        gen0 = _vmap_init(scenario.init_fn, sparams, mesh)
        carry0 = (gen0, J0)

        def call(carry, t0, slabs):
            return fwd(sparams, *grid_args, t0, carry)

        make_slab = None
    else:
        has_svc = padded.svc is not None
        fwd = _compiled_dp_stream_fwd(has_svc, mesh, dp_backend, donate)
        bwd = _compiled_dp_stream_bwd(has_svc, mesh, dp_backend)
        carry0 = J0

        def call(carry, t0, slabs):
            return fwd(*grid_args, t0, carry, *slabs)

        make_slab = _obs_slab_builder(padded, chunk, mesh, with_side=False)

    stepper = FleetStepper(call=call, carry=carry0, chunk=chunk, mesh=mesh,
                           has_out=False, kind="dp",
                           scenario_mode=scenario is not None, donate=donate,
                           B=B_pad, B_pad=B_pad, K=K, T_max=T_pad,
                           T_orig=T_orig)
    empty = lambda i: ()
    ckpts = []                 # device-resident [B, K] rows (+ gen states)
    for slabs in slab_feed(make_slab or empty, n_chunks,
                           async_ingest and make_slab is not None):
        if collect_schedule:   # cost-only never backtracks — don't retain
            ckpts.append(stepper.carry)  # dead device rows
        stepper.step_slabs(slabs)
    # local rows: each process backtracks (and returns) its own shard
    J_T = _local_rows(stepper.carry[1] if scenario is not None
                      else stepper.carry)
    cost = J_T.min(axis=1)
    if not collect_schedule:
        return cost, None
    k = _dev_rows(mesh, J_T.argmin(axis=1).astype(np.int32))
    r_parts = []
    rev = (empty if make_slab is None
           else (lambda j: make_slab(n_chunks - 1 - j)))
    with shard_ctx(mesh, (FLEET_AXIS,), model_axis=None):
        for j, slabs in enumerate(
                slab_feed(rev, n_chunks,
                          async_ingest and make_slab is not None)):
            i = n_chunks - 1 - j
            t0 = np.int32(i * chunk)
            if scenario is not None:
                gen_ck, Jck = ckpts[i]
                k, rck = bwd(sparams, *grid_args, t0, gen_ck, Jck, k)
            else:
                k, rck = bwd(*grid_args, t0, ckpts[i], k, *slabs)
            r_parts.append(_local_rows(rck))
    r_hist = np.concatenate(r_parts[::-1], axis=1)
    return cost, r_hist


def offline_dp_memory_stats(fleet: FleetBatch, *,
                            scenario: Optional[Scenario] = None,
                            mesh: Optional[Mesh] = None,
                            chunk_size: Optional[int] = None,
                            checkpointed: bool = False,
                            collect_schedule: bool = True,
                            n_seeds: Optional[int] = None,
                            antithetic: bool = False,
                            dp_backend: str = "xla",
                            prng_backend: str = "xla") -> dict:
    """XLA-reported memory of the compiled device-scan DP core for this
    config, WITHOUT running it: ``{"argument_bytes", "output_bytes",
    "temp_bytes"}``.  The probed program is built by the same
    ``_dp_scan_core_args`` (and the same MC replication) the solver uses,
    so the numbers describe exactly the executed computation —
    ``kernel_bench.offline_dp_streaming`` asserts its peak-memory ratio
    (materialized vs checkpointed backpointers) on ``temp_bytes``, where
    scan-carried intermediates such as the [B, T, K] argmin table live.
    Note the stats are per *program*: on a multi-device mesh each device
    runs one program over its B/n_devices shard."""
    if not collect_schedule and not checkpointed:
        # same contract as offline_opt_fleet — never report a program the
        # solver would refuse to run
        raise ValueError("collect_schedule=False requires checkpointed=True")
    _check_backends(dp_backend, prng_backend, scenario)
    fleet, scenario, _ = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    if scenario is not None:
        _check_scenario(scenario, fleet)
        scenario = with_prng_backend(scenario, prng_backend)
    mesh, padded, n_chunks, T_pad = _prepare_fleet(fleet, mesh, chunk_size)
    core, args = _dp_scan_core_args(scenario, padded, mesh, n_chunks, T_pad,
                                    checkpointed, collect_schedule,
                                    dp_backend)
    stats = core.lower(*args).compile().memory_analysis()
    return {"argument_bytes": int(stats.argument_size_in_bytes),
            "output_bytes": int(stats.output_size_in_bytes),
            "temp_bytes": int(stats.temp_size_in_bytes)}


def offline_opt_fleet(fleet: FleetBatch, *,
                      scenario: Optional[Scenario] = None,
                      mesh: Optional[Mesh] = None,
                      chunk_size: Optional[int] = None,
                      n_seeds: Optional[int] = None,
                      antithetic: bool = False,
                      checkpointed: bool = False,
                      stream: bool = False,
                      collect_schedule: bool = True,
                      dp_backend: str = "xla",
                      prng_backend: str = "xla",
                      async_ingest: bool = False,
                      gather: bool = False) -> FleetOfflineResult:
    """Fleet alpha-OPT: the exact DP, sharded over devices and chunked over
    time, each instance solved at its own horizon.  With ``scenario=...``
    the observations are generated on device inside the forward recursion
    (and again inside the schedule evaluation) — bit-identical to the
    materialized run.  ``n_seeds=S`` solves S seed-replicas of every
    instance (same key-fold convention as ``run_fleet``; ``antithetic=True``
    pairs them — see ``scenarios.replicate_seeds``).

    ``checkpointed=True`` switches to the two-pass checkpointed recursion:
    the forward pass keeps one [B, K] value-frontier checkpoint per chunk
    and the backtrack replays each chunk in reverse from its checkpoint,
    recomputing argmins on the fly — **bit-identical** to the materialized
    path wherever both fit, but never materializing a [B, T, K] (or any
    [B, T]-shaped backpointer) array, which is what extends exact OPT to
    T = 10^6-10^7 horizons.  It composes with every other axis: mesh,
    mixed horizons, ``n_seeds``, ``chunk_size`` (the checkpoint grain) and
    ``stream=True`` (host-driven passes — requires ``chunk_size``; obs and
    ``r_hist`` then cross the host boundary one [B, chunk] slab at a time).
    ``collect_schedule=False`` (checkpointed only) skips the backtrack and
    the schedule evaluation altogether and returns cost-only results
    (``r_hist`` / ``sim`` are None) — the cheapest way to price OPT at
    horizons where even the [B, T] schedule is unwelcome.

    ``dp_backend`` selects the min-plus relaxation engine inside every
    driver above ("xla" default / "pallas" — see ``dp_fwd_chunk``);
    ``prng_backend`` the scenario's counter-keyed uniform engine (as in
    ``run_fleet``).  Backends are a pure performance knob: costs,
    schedules and sim results are bit-identical across every combination
    (tests/test_backend_dispatch.py).

    ``async_ingest=True`` (streamed, obs-backed fleets) prefetches the
    host->device obs slabs of both DP passes on a background thread —
    double buffering, bit-identical to the synchronous feed (see
    ``core/ingest.py``); a no-op for scenario-fused solves, which ship no
    slabs.

    ``gather=True`` (process-spanning meshes) allgathers cost / r_hist /
    sim rows to the full [B_global] fleet on every process; the default is
    this process's own rows, as in ``run_fleet``."""
    if stream and not checkpointed:
        raise ValueError("stream=True requires checkpointed=True (the "
                         "materialized backtrack needs the whole table)")
    if stream and chunk_size is None:
        raise ValueError("stream=True requires chunk_size")
    if async_ingest and not stream:
        raise ValueError("async_ingest=True requires stream=True (only the "
                         "host-driven passes feed slabs)")
    if not collect_schedule and not checkpointed:
        raise ValueError("collect_schedule=False requires checkpointed=True")
    _check_backends(dp_backend, prng_backend, scenario)
    fleet, scenario, S = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    B, T_max = fleet.B, fleet.T_max
    mesh, padded, n_chunks, T_pad = _prepare_fleet(fleet, mesh, chunk_size)
    if scenario is not None:
        _check_scenario(scenario, fleet)
        scenario = with_prng_backend(scenario, prng_backend)
    if stream:
        cost, r_hist = _dp_ckpt_streamed(scenario, padded, mesh, n_chunks,
                                         T_pad, collect_schedule, dp_backend,
                                         async_ingest)
    else:
        core, args = _dp_scan_core_args(scenario, padded, mesh, n_chunks,
                                        T_pad, checkpointed, collect_schedule,
                                        dp_backend)
        out = _dispatch(mesh, core, *args)
        cost, r_hist = out if collect_schedule else (out, None)
    with _span("fleet.readback"):
        cost = _local_rows(cost)[:B].astype(np.float64)
    if not collect_schedule:
        if gather:
            cost = _gather_rows(mesh, cost)
        return FleetOfflineResult(cost=cost, r_hist=None, sim=None,
                                  n_seeds=S)
    r_hist = _local_rows(r_hist)[:B, :T_max].astype(np.int64)
    # fleet/scenario are already seed-replicated here, so the evaluation
    # runs plain and only the result is re-tagged with the MC axis
    sim = evaluate_schedule_fleet(fleet, r_hist, scenario=scenario, mesh=mesh,
                                  chunk_size=chunk_size)
    sim = dataclasses.replace(sim, n_seeds=S)
    if gather:
        cost = _gather_rows(mesh, cost)
        r_hist = _gather_rows(mesh, r_hist)
        sim = _gather_result(sim, mesh)
    return FleetOfflineResult(cost=cost, r_hist=r_hist, sim=sim, n_seeds=S)


# ----------------------------------------------------------------------
# Schedule evaluation on a fleet.
# ----------------------------------------------------------------------

def _make_schedule_instance_core(n_chunks: int, has_svc: bool):
    def core(lv, g, M, T_len, r, x, c, *opt):
        K = lv.shape[-1]
        svc = opt[0] if has_svc else None
        carry0 = (jnp.asarray(0, jnp.int32), sim_acc0(K, lv.dtype))

        def run_chunk(carry, t0, rck, xck, cck, sck):
            if sck is None:
                sck = _model1_svc(xck, g)
            return schedule_chunk_core(lv, M, T_len, t0, carry, rck, cck, sck)

        carry, _ = _chunked_drive(run_chunk, carry0, n_chunks, (r, x, c, svc))
        (_, acc) = carry
        return acc["sums"], acc["counts"]

    return core


def _make_schedule_scenario_core(sc_init, sc_chunk, n_chunks: int):
    """Schedule evaluation with fused generation: the schedule ``r`` stays
    a resident array (it is the *input*), the obs it is priced on are
    generated chunk-by-chunk."""

    def core(sparams, lv, g, M, T_len, r, tids_all):
        K = lv.shape[-1]
        carry0 = (sc_init(sparams),
                  (jnp.asarray(0, jnp.int32), sim_acc0(K, lv.dtype)))

        def run_chunk(carry, t0, rck, tids):
            gen_state, sched = carry
            gen_state, slab = sc_chunk(sparams, gen_state, tids)
            sck = slab.svc if slab.svc is not None else _model1_svc(slab.x, g)
            sched, _ = schedule_chunk_core(lv, M, T_len, t0, sched, rck,
                                           slab.c, sck)
            return (gen_state, sched), None

        carry, _ = _chunked_drive(run_chunk, carry0, n_chunks, (r, tids_all))
        (_, (_, acc)) = carry
        return acc["sums"], acc["counts"]

    return core


@functools.lru_cache(maxsize=32)
def _compiled_schedule_core(n_chunks: int, has_svc: bool, mesh: Mesh):
    core = _make_schedule_instance_core(n_chunks, has_svc)
    spec = P(FLEET_AXIS)
    sharded = _fleet_map(jax.vmap(core), mesh=mesh,
                         in_specs=(spec,) * (7 + int(has_svc)),
                         out_specs=(spec, spec))
    return jax.jit(sharded)


@functools.lru_cache(maxsize=32)
def _compiled_schedule_scenario_core(sc_init, sc_chunk, n_chunks: int,
                                     mesh: Mesh):
    core = _make_schedule_scenario_core(sc_init, sc_chunk, n_chunks)
    spec = P(FLEET_AXIS)
    sharded = _fleet_map(jax.vmap(core, in_axes=(0, 0, 0, 0, 0, 0, None)),
                         mesh=mesh, in_specs=(spec,) * 6 + (P(),),
                         out_specs=(spec, spec))
    return jax.jit(sharded)


def evaluate_schedule_fleet(fleet: FleetBatch, r_hist, *,
                            scenario: Optional[Scenario] = None,
                            mesh: Optional[Mesh] = None,
                            chunk_size: Optional[int] = None,
                            n_seeds: Optional[int] = None,
                            antithetic: bool = False,
                            prng_backend: str = "xla",
                            gather: bool = False) -> FleetResult:
    """Fleet ``evaluate_schedule``: ``r_hist`` is [B, T_max]; slots past each
    instance's T contribute nothing (and charge no fetch).  With
    ``scenario=...`` the priced observations are generated on device;
    ``n_seeds=S`` prices the schedules on S seed-replicas of the scenario
    (``r_hist`` rows may be [B] — repeated per replica — or the full
    [B*S] replication; ``antithetic=True`` pairs the replicas as in
    ``run_fleet``).  ``prng_backend`` selects the counter-keyed uniform
    engine as in ``run_fleet`` (bit-identical performance knob)."""
    dt = default_float_dtype()
    B_orig = fleet.B
    _check_backends("xla", prng_backend, scenario)
    fleet, scenario, S = _replicate_mc(fleet, scenario, n_seeds, antithetic)
    if scenario is not None:
        scenario = with_prng_backend(scenario, prng_backend)
    B, T_max = fleet.B, fleet.T_max
    mesh, padded, n_chunks, T_pad = _prepare_fleet(fleet, mesh, chunk_size)
    r = np.asarray(r_hist, np.int32)
    if S > 1 and r.shape[0] == B_orig:
        r = np.repeat(r, S, axis=0)
    if T_pad > T_max:
        r = np.pad(r, ((0, 0), (0, T_pad - T_max)))
    r = _pad_rows(r, padded.B, np)
    if scenario is not None:
        _check_scenario(scenario, fleet)
        sparams = _dev_tree(mesh, _pad_params(scenario.params, padded.B))
        core = _compiled_schedule_scenario_core(scenario.init_fn,
                                                scenario.chunk_fn,
                                                n_chunks, mesh)
        args = (sparams, _dev_rows(mesh, padded.grid.levels.astype(dt)),
                _dev_rows(mesh, padded.grid.g.astype(dt)),
                _dev_rows(mesh, padded.grid.M.astype(dt)),
                _dev_rows(mesh, padded.T), _dev_rows(mesh, r),
                _dev_replicated(mesh, np.arange(T_pad, dtype=np.int32)))
    else:
        has_svc = fleet.svc is not None
        core = _compiled_schedule_core(n_chunks, has_svc, mesh)
        args = (_dev_rows(mesh, padded.grid.levels.astype(dt)),
                _dev_rows(mesh, padded.grid.g.astype(dt)),
                _dev_rows(mesh, padded.grid.M.astype(dt)),
                _dev_rows(mesh, padded.T), _dev_rows(mesh, r),
                _dev_rows(mesh, padded.x), _dev_rows(mesh, padded.c))
        if has_svc:
            args += (_dev_rows(mesh, padded.svc),)
    with shard_ctx(mesh, (FLEET_AXIS,), model_axis=None):
        sums, counts = core(*args)
    # r (replicated + padded above) rather than the raw r_hist input, so the
    # returned trace matches the [B*S] row layout of the totals
    res = _fleet_result(r.astype(np.int64), sums, counts,
                        B, T_max, fleet.T, S)
    return _gather_result(res, mesh) if gather else res
