"""Offline optimal hosting (alpha-OPT / OPT) by exact dynamic programming.

State = level index, K states; transition cost = fetch on increments only
(eviction free).  ``J_t(k) = min_k' [J_{t-1}(k') + M (lv_k - lv_k')^+] + w_t[k]``
with ``J_0 = [0, inf, ...]`` (service starts off-edge, like all policies).

Both passes are ``lax.scan``s: the forward value recursion emits the argmin
table, and the backtrack is a *reverse* scan over that table (no Python
loop), so the whole DP jits — and vmaps over a stacked ``HostingGrid``
(``offline_opt_batch``), with padded levels priced at +inf so mixed-K
batches stay exact.  Argmins are kept so the optimal schedule feeds the
hosting-status histograms (Figs 2, 8, 12-22).

``core.fleet.offline_opt_fleet`` is the fleet form of this DP: the same
forward recursion op-for-op, device-sharded over the instance axis, chunked
over time, and frozen past each instance's own horizon (identity
backpointers on padded slots) — bit-identical to ``offline_opt_batch`` on
uniform-horizon fleets.  The chunk-level kernel lives HERE
(``dp_fwd_chunk`` / ``dp_backtrack_chunk``): one forward recursion shared
verbatim by the materialized-backpointer cores and the checkpointed ones,
so every driver is op-for-op the same recursion.

**Checkpointed backtracking** (``offline_opt_fleet(checkpointed=True)``)
removes the last O(T) DP buffer: the forward value pass stores only one
[K] value-frontier checkpoint per chunk (plus the generator state for
scenario-fused runs), and the backtrack pass replays each chunk *in
reverse order* from its checkpoint, recomputing that chunk's argmin table
on the fly — device memory is O(chunk * K + n_chunks * K) per instance
instead of O(T * K), at the price of a second forward sweep.  Because the
recomputed tables are produced by the identical ``dp_fwd_chunk`` from the
identical frontier, the checkpointed schedule is **bit-identical** to the
materialized one wherever both fit, which is what extends exact OPT to the
same T = 10^6-10^7 horizons as ``run_fleet(collect_trace=False)``.

**Kernel/reference split** — the ``lax.scan`` in ``dp_fwd_chunk`` is the
recursion: every driver runs it, and it alone emits the argmin tables the
backtrack reads.  One kernel stands beside it, taken only where the code
observes that it wins.  The passes that discard the argmin table (pass 1
of the checkpointed cores, the streamed forward steps) call
``dp_fwd_frontier``; on TPU a batch of rows whose frontier width lies in
``[DP_ROWS_MIN_K, DP_ROWS_MAX_K]`` (the joint multi-service states) runs
there through ``kernels.hosting.dp_minplus_rows`` instead of the vmapped
scan: rows on lanes, each row block's fetch matrices read from HBM once
per chunk instead of once per slot.  The choice follows the width and the
platform the program lowers for (``dp_rows_platforms``), and the
frontiers are bitwise the scan's (tests/test_dp_rows_kernel.py);
narrower frontiers keep the scan's program unchanged.

``OPT`` (no partial hosting, the benchmark of [22]) is the same DP on the
2-level instance. Exhaustive-search cross-checks live in the tests.
"""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import (HostingCosts, HostingGrid, ServiceSet,
                              default_float_dtype, per_slot_cost_matrix)


def _eval(costs, r_hist, x, c, svc=None):
    # local import: simulator imports policies.base, whose package __init__
    # imports this module — keep the edge lazy to break the cycle.
    from repro.core.simulator import evaluate_schedule
    return evaluate_schedule(costs, r_hist, x, c, svc)


@dataclasses.dataclass
class OfflineResult:
    cost: float
    r_hist: np.ndarray
    sim: object  # repro.core.simulator.SimResult


@dataclasses.dataclass
class BatchOfflineResult:
    cost: np.ndarray          # [B]
    r_hist: np.ndarray        # [B, T]
    sim: object               # repro.core.simulator.BatchSimResult


# ----------------------------------------------------------------------
# The chunk-level DP kernel (shared by every fleet driver in core/fleet.py).
# ----------------------------------------------------------------------

def dp_frontier0(K: int, dtype=jnp.float32):
    """The initial value frontier ``J_0 = [0, inf, ...]`` (service starts
    off-edge, like every policy)."""
    return jnp.full((K,), jnp.inf, dtype).at[0].set(0.0)


def dp_fetch_matrix(M32, lv32):
    """``fetch_mat[k_prev, k_next] = M * (lv_next - lv_prev)^+``.

    A matrix-valued ``M32`` (per-instance ``[K, K]``, ``ndim >= 2``) is an
    *explicit* fetch matrix and passes through untouched — the joint
    multi-service grids of ``costs.ServiceSet`` (whose host-side
    construction uses exactly this function's float32 op order per
    service, so an N=1 joint matrix is bitwise the rank-one product
    below).  Every DP driver builds its fetch matrix here, inside its
    per-instance vmap, which is what threads matrix-M grids through the
    materialized, checkpointed, streamed, scenario-fused and row-kernel
    paths with no driver changes."""
    if jnp.ndim(M32) >= 2:
        return M32
    return M32 * jnp.maximum(lv32[None, :] - lv32[:, None], 0.0)


def dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len):
    """One chunk of the forward value recursion — THE one copy every fleet
    DP driver shares (materialized backpointers, checkpointed two-pass,
    obs-backed and scenario-fused, scan and streamed), so all of them are
    op-for-op the same recursion.  Invalid slots (``t >= T_len``) keep the
    frontier frozen and write identity argmins; padded K levels are priced
    ``+inf`` via ``kmask`` exactly as in ``offline_opt_batch``.

    Returns ``(J', args [chunk, K])``.
    """
    # a named scope labels the recursion's ops in a profiler trace
    with jax.named_scope("dp_fwd"):
        K = lv32.shape[-1]
        # the same float32 w as offline_opt_batch: rent + svc, +inf pads
        wck = (cck[:, None].astype(jnp.float32) * lv32[None, :]
               + sck.astype(jnp.float32))
        wck = jnp.where(kmask[None, :], wck, jnp.inf)

        def fwd(J_prev, inp):
            t, w_t = inp
            valid_t = t < T_len
            trans = J_prev[:, None] + fetch_mat
            arg = jnp.argmin(trans, axis=0)
            J = jnp.min(trans, axis=0) + w_t
            J = jnp.where(valid_t, J, J_prev)
            arg = jnp.where(valid_t, arg, jnp.arange(K))
            return J, arg

        return jax.lax.scan(fwd, J, (tids, wck))


#: Frontier widths at which the batched, frontier-only forward runs the
#: row-blocked kernel ``kernels.hosting.dp_minplus_rows`` on TPU.  From 8:
#: on a v5e the kernel beats the vmapped scan at every width swept from 8
#: to 50, while at 3 it is no faster than the scan inside a pricing
#: program (PERF.md, the K sweep).  Up to 64: a row block's fetch matrices
#: then take at most 2 MiB of VMEM per buffer.
DP_ROWS_MIN_K = 8
DP_ROWS_MAX_K = 64

# test hook: trace-time counts of the row-blocked forward, keyed by
# (frontier width K, chunk)
DP_ROWS_TRACES = collections.Counter()


def dp_rows_platforms(K: int) -> tuple:
    """The platforms on which a batched, frontier-only forward of width
    ``K`` runs ``dp_minplus_rows``: TPU for ``DP_ROWS_MIN_K <= K <=
    DP_ROWS_MAX_K``, none otherwise.  Elsewhere the ``lax.scan`` stays."""
    return ("tpu",) if DP_ROWS_MIN_K <= K <= DP_ROWS_MAX_K else ()


def dp_fwd_frontier(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len):
    """``dp_fwd_chunk`` for the passes that discard the argmin table: the
    frontier ``J'`` alone, bitwise the same.  Under ``jax.vmap``, a width
    that ``dp_rows_platforms`` lists runs every row of the batch through
    the row-blocked kernel on those platforms (``_dp_fwd_rows``); every
    other call is ``dp_fwd_chunk``."""
    if dp_rows_platforms(lv32.shape[-1]):
        return _dp_fwd_rows(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len)
    return dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len)[0]


def _dp_fwd_scan(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len):
    return dp_fwd_chunk(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len)[0]


_dp_fwd_rows = jax.custom_batching.custom_vmap(_dp_fwd_scan)


def _dp_fwd_rows_kernel(J, tids, cck, sck, lv32, kmask, fetch_mat, T_len, *,
                        interpret: bool):
    """The batched frontier-only forward through ``dp_minplus_rows``: every
    operand is built rows-minor (one row per lane) as the kernel reads it,
    with ``dp_fwd_chunk``'s float32 ops for the holding costs."""
    from repro.kernels.hosting import dp_minplus_rows
    with jax.named_scope("dp_fwd"), jax.named_scope("dp_fwd_rows"):
        f32 = jnp.float32
        wT = (cck.T.astype(f32)[:, None, :] * lv32.T[None, :, :]
              + jnp.transpose(sck.astype(f32), (1, 2, 0)))
        wT = jnp.where(kmask.T[None, :, :], wT, jnp.inf)
        out = dp_minplus_rows(J.T, wT, jnp.transpose(fetch_mat, (1, 2, 0)),
                              T_len - tids[:, 0], interpret=interpret)
        return out.T


@_dp_fwd_rows.def_vmap
def _dp_fwd_rows_vmap(axis_size, in_batched, J, tids, cck, sck, lv32, kmask,
                      fetch_mat, T_len):
    """The batching rule: the kernel on the platforms that
    ``dp_rows_platforms`` lists (in interpret mode off the chip), the
    vmapped scan on every other; the choice is made where the program
    lowers.  Unbatched operands (the fleet cores share ``tids``) are
    broadcast to the batch first."""
    args = (J, tids, cck, sck, lv32, kmask, fetch_mat, T_len)
    K, chunk = lv32.shape[-1], tids.shape[-1]
    DP_ROWS_TRACES[(K, chunk)] += 1
    rows = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip(args, in_batched)]
    kernels = {p: functools.partial(_dp_fwd_rows_kernel,
                                    interpret=p != "tpu")
               for p in dp_rows_platforms(K)}
    out = jax.lax.platform_dependent(*rows, default=jax.vmap(_dp_fwd_scan),
                                     **kernels)
    return out, True


def dp_backtrack_chunk(k, args):
    """Backtrack one ``[chunk, K]`` argmin table from terminal level ``k``:
    returns ``(k at chunk entry, r_hist [chunk])``.  The checkpointed
    drivers chain this right-to-left over recomputed per-chunk tables; the
    materialized drivers call it once on the whole-horizon table — the
    (k, arg) op sequence is identical either way."""

    def back(k, arg_t):
        return arg_t[k], k

    return jax.lax.scan(back, k, args, reverse=True)


def dp_backtrack(J_T, args):
    """Terminal min + whole-table backtrack (the materialized path)."""
    k_T = jnp.argmin(J_T)
    _, r_hist = dp_backtrack_chunk(k_T, args)
    return jnp.min(J_T), r_hist.astype(jnp.int32)


def _dp_core(M, lv, w):
    """Forward DP + reverse-scan backtrack for one instance.

    Args: M scalar (or an explicit [K, K] fetch matrix — joint
    multi-service states), lv [K], w [T, K] per-slot holding costs (+inf on
    padded levels).  Returns (cost scalar, r_hist [T]).
    """
    K = lv.shape[-1]
    fetch_mat = dp_fetch_matrix(M, lv)

    def fwd(J_prev, w_t):
        # trans[k_prev, k_next] = J_prev[k_prev] + fetch
        trans = J_prev[:, None] + fetch_mat
        arg = jnp.argmin(trans, axis=0)          # [K] best predecessor per level
        J = jnp.min(trans, axis=0) + w_t
        return J, arg

    J0 = jnp.full((K,), jnp.inf, w.dtype).at[0].set(0.0)
    J_T, args = jax.lax.scan(fwd, J0, w)

    def back(k, arg_t):
        return arg_t[k], k

    k_T = jnp.argmin(J_T)
    _, r_hist = jax.lax.scan(back, k_T, args, reverse=True)
    return jnp.min(J_T), r_hist.astype(jnp.int32)


_dp_one = jax.jit(_dp_core)
_dp_vmapped = jax.jit(jax.vmap(_dp_core))


def offline_opt(costs: HostingCosts, x, c, svc=None) -> OfflineResult:
    """Exact alpha-OPT over the instance; also returns the argmin schedule."""
    dt = default_float_dtype()
    x = jnp.asarray(x, jnp.int32)
    c = jnp.asarray(c, dt)
    w = per_slot_cost_matrix(costs, x, c, None if svc is None else jnp.asarray(svc))
    lv = jnp.asarray(costs.levels, jnp.float32)
    cost, r_hist = _dp_one(jnp.asarray(costs.M, jnp.float32), lv, w)
    r_hist = np.asarray(r_hist).astype(np.int64)
    sim = _eval(costs, r_hist, x, c, svc)
    return OfflineResult(cost=float(cost), r_hist=r_hist, sim=sim)


def offline_opt_batch(grid: HostingGrid, x, c, svc=None) -> BatchOfflineResult:
    """Batched alpha-OPT: the DP + backtrack vmapped over a stacked grid.

    ``x``/``c`` are [T] or [B, T]; ``svc`` optional [B, T, K].  Padded levels
    of mixed-K grids are priced at +inf, so each instance's schedule uses
    only its real levels.
    """
    from repro.core.simulator import _batch_obs, evaluate_schedule_batch
    x, c, svc_full, _ = _batch_obs(grid, x, c, svc, None)
    lv = grid.levels.astype(jnp.float32)
    rent = c[:, :, None].astype(jnp.float32) * lv[:, None, :]
    w = rent + svc_full.astype(jnp.float32)                     # [B, T, K]
    w = jnp.where(grid.mask[:, None, :], w, jnp.inf)
    cost, r_hist = _dp_vmapped(grid.M.astype(jnp.float32), lv, w)
    sim = evaluate_schedule_batch(grid, r_hist, x, c, svc)
    return BatchOfflineResult(cost=np.asarray(cost).astype(np.float64),
                              r_hist=np.asarray(r_hist).astype(np.int64),
                              sim=sim)


def offline_opt_no_partial(costs: HostingCosts, x, c, svc=None) -> OfflineResult:
    """OPT of [22]: offline optimum restricted to levels {0, 1}."""
    c2 = HostingCosts.two_level(costs.M, costs.c_min, costs.c_max)
    svc2 = None
    if svc is not None:
        svc = np.asarray(svc)
        svc2 = svc[:, [0, costs.K - 1]]
    return offline_opt(c2, x, c, svc2)


# ----------------------------------------------------------------------
# Joint multi-service OPT: the same DP on a ServiceSet's feasible joint
# states (explicit fetch matrix, shared-capacity constraint baked into the
# state enumeration — see costs.ServiceSet).
# ----------------------------------------------------------------------

@dataclasses.dataclass
class JointOfflineResult:
    """Joint capacity-respecting optimum of one ``ServiceSet``.

    ``states`` are joint-state indices into ``sset.joint_states()``;
    ``r_hist[n]`` is service n's per-slot level-index schedule (every slot
    feasible by construction — infeasible combinations are never states).
    """

    cost: float
    states: np.ndarray        # [T] joint-state indices
    r_hist: np.ndarray        # [N, T] per-service level indices


def _joint_slot_costs(sset: ServiceSet, xs, c, svcs):
    """([T, J] float32 holding costs, [N, T] arrivals) for the joint DP.

    Op order matches the single-service w assembly exactly (rent product
    first, then one svc addition per service, n-ascending): at N=1 the
    matrix is bitwise ``per_slot_cost_matrix``'s.
    """
    idx = sset.joint_states()
    xs = np.asarray(xs)
    if xs.ndim == 1:
        xs = np.broadcast_to(xs[None], (sset.N,) + xs.shape)
    if xs.shape[0] != sset.N:
        raise ValueError(f"xs has {xs.shape[0]} arrival rows for "
                         f"{sset.N} services")
    c32 = np.asarray(c, np.float32)
    w = c32[:, None] * sset.joint_levels()[None, :]            # [T, J]
    for n, cc in enumerate(sset.services):
        if svcs is not None and svcs[n] is not None:
            svc_n = np.asarray(svcs[n], np.float32)
        else:
            svc_n = (xs[n][:, None].astype(np.float32)
                     * np.asarray(cc.g, np.float32)[None, :])
        w = w + svc_n[:, idx[:, n]]
    return w, xs


def offline_opt_joint(sset: ServiceSet, xs, c,
                      svcs=None) -> JointOfflineResult:
    """Exact joint OPT for N services sharing one edge: the standard DP
    (``_dp_core`` — the same jitted core as ``offline_opt``) over the
    feasible joint states, with the capacity constraint enforced by the
    state enumeration and fetches priced by the explicit joint matrix.

    Args:
      xs: [T] (common arrivals) or [N, T] per-service arrival counts.
      c: [T] rent costs (one edge, one rent stream).
      svcs: optional list of per-service realized [T, K_n] service costs
        (Model 2); ``None`` entries fall back to Model-1 ``g_n * x_n``.

    At N=1 (unconstrained) this is bitwise ``offline_opt`` — same w, same
    fetch matrix, same DP ops (tests/test_multi_service.py).
    """
    w, _ = _joint_slot_costs(sset, xs, c, svcs)
    fm = jnp.asarray(sset.joint_fetch_matrix())
    lv = jnp.asarray(sset.joint_levels())
    cost, states = _dp_one(fm, lv, jnp.asarray(w))
    states = np.asarray(states).astype(np.int64)
    return JointOfflineResult(cost=float(cost), states=states,
                              r_hist=sset.joint_states()[states].T
                                         .astype(np.int64))


def brute_force_joint_opt(sset: ServiceSet, xs, c,
                          svcs=None) -> JointOfflineResult:
    """Exhaustive joint oracle (tests only; tiny J**T): enumerates every
    joint-state sequence, accumulating in float32 with the DP's exact
    association ``(cost + fetch) + w`` per slot — so the minimum equals
    ``offline_opt_joint``'s cost EXACTLY (float equality, no tolerance),
    which is what the oracle suites assert."""
    w, xs = _joint_slot_costs(sset, xs, c, svcs)
    fm = sset.joint_fetch_matrix()
    T = w.shape[0]
    J = fm.shape[0]
    best, best_seq = np.inf, None
    for code in range(J ** T):
        cost = np.float32(0.0)
        prev = 0
        seq = np.empty((T,), np.int64)
        for t in range(T):
            k = (code // (J ** t)) % J
            cost = (cost + fm[prev, k]) + w[t, k]
            prev = k
            seq[t] = k
        if cost < best:
            best, best_seq = cost, seq
    return JointOfflineResult(cost=float(best), states=best_seq,
                              r_hist=sset.joint_states()[best_seq].T
                                         .astype(np.int64))


def brute_force_opt(costs: HostingCosts, x, c, svc=None) -> OfflineResult:
    """Exhaustive search over all K^T schedules (tests only; tiny T)."""
    x = np.asarray(x)
    T = len(x)
    K = costs.K
    best, best_seq = np.inf, None
    for code in range(K ** T):
        seq = np.base_repr(code, K).zfill(T)
        r = np.array([int(ch) for ch in seq], np.int64)
        res = _eval(costs, r, x, c, svc)
        if res.total < best - 1e-9:
            best, best_seq = res.total, r
    sim = _eval(costs, best_seq, x, c, svc)
    return OfflineResult(cost=best, r_hist=best_seq, sim=sim)
