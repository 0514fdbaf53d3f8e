"""The Pallas kernel of the hosting engine: ``dp_minplus_rows``.

It runs the offline-OPT forward recursion (``offline_opt.dp_fwd_chunk``'s
scan body) for many rows at once when only the frontier is wanted (the
forward passes that keep no argmin table): rows on the 128 lanes, the
frontier ``[K, 128]`` on sublanes, and each row block's ``[K, K, 128]``
fetch matrices resident in VMEM for the whole chunk, so a wide joint-state
DP reads them from HBM once per chunk instead of once per slot.  Frontier
freezing past each row's horizon and ``+inf`` pricing of masked states
ride in unchanged, and the frontiers are **bitwise** the scan's
(tests/test_dp_rows_kernel.py, tests/test_kernels.py).

The kernel takes the batch itself, rows-minor; the engine reaches it
through a batching rule (``offline_opt.dp_fwd_frontier``) on the
platforms and widths where it was measured to beat the vmapped scan
(``offline_opt.dp_rows_platforms``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.utils import default_interpret, pad_to


#: Rows of one block: the lane width, so no lane of a vector is padding.
DP_ROWS_LANES = 128
#: Bytes of one holding-cost block of ``dp_minplus_rows`` in VMEM; the
#: slot tile is the largest power of two under it.
DP_ROWS_W_BLOCK_BYTES = 2 * 2**20


def dp_rows_slot_tile(chunk: int, K: int) -> int:
    """Slots per holding-cost block of ``dp_minplus_rows``: a power of two
    whose ``[slots, K, 128]`` float32 block (K padded to 8 sublanes) fits
    ``DP_ROWS_W_BLOCK_BYTES``, and never more than the chunk."""
    row_bytes = -(-K // 8) * 8 * DP_ROWS_LANES * 4
    tile = 1 << max(0, (DP_ROWS_W_BLOCK_BYTES // row_bytes).bit_length() - 1)
    return min(chunk, tile)


def _dp_rows_kernel(n_live_ref, j_ref, w_ref, f_ref, jout_ref, fr_s, *,
                    slots: int, K: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        fr_s[...] = j_ref[...]

    n_live = n_live_ref[...]                      # [1, 128] int32
    t0 = s * slots

    def body(t, J):                               # J [K, 128]: rows on lanes
        # min over predecessors i of J[i] + F[i, :]: the same float32 sums
        # as the scan's ``J_prev[:, None] + fetch``; a minimum rounds
        # nothing, so the order over i cannot change a bit
        m = J[0:1, :] + f_ref[0]
        for i in range(1, K):
            m = jnp.minimum(m, J[i:i + 1, :] + f_ref[i])
        Jn = m + w_ref[t]
        return jnp.where(t0 + t < n_live, Jn, J)

    fr_s[...] = jax.lax.fori_loop(0, slots, body, fr_s[...])

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        jout_ref[...] = fr_s[...]


def dp_minplus_rows(J, w, fetch, n_live, *, interpret=None):
    """Many rows, one chunk of the DP forward recursion, frontier only.

    Args, all rows-minor (one row per lane): ``J`` [K, R] float32 entry
    frontiers; ``w`` [chunk, K, R] float32 per-slot holding costs
    (``+inf`` on masked states); ``fetch`` [K, K, R] float32,
    ``fetch[i, j, r]`` the cost of moving row r from state i to j;
    ``n_live`` [R] int32 — slot ``t`` of row r is live when
    ``t < n_live[r]``, the rest keep the frontier.
    Returns ``J'`` [K, R], bitwise the frontier of the ``lax.scan`` in
    ``offline_opt.dp_fwd_chunk`` for every row; no argmin table.

    Rows go to blocks of 128 lanes (``R`` is padded with ``+inf``
    frontiers and cut back).  The grid is (row blocks, slot tiles): a
    block's fetch matrices keep one block index over the slot axis, so
    they cross HBM once per chunk and stay in VMEM, and the frontier stays
    in VMEM scratch from one slot tile to the next; only ``w`` streams.
    """
    chunk, K, R = w.shape
    if interpret is None:
        interpret = default_interpret()
    L = DP_ROWS_LANES
    slots = dp_rows_slot_tile(chunk, K)
    f32 = jnp.float32
    # padded slots are frozen (n_live <= chunk); padded rows never live
    n_live = jnp.minimum(jnp.asarray(n_live, jnp.int32), chunk)
    n_live, _ = pad_to(n_live, 0, L)
    J, _ = pad_to(J.astype(f32), 1, L, jnp.inf)
    w, _ = pad_to(pad_to(w.astype(f32), 0, slots)[0], 2, L)
    fetch, _ = pad_to(fetch.astype(f32), 2, L)
    R_pad = J.shape[1]
    kernel = functools.partial(_dp_rows_kernel, slots=slots, K=K)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((K, R_pad), f32),
        grid=(R_pad // L, w.shape[0] // slots),
        in_specs=[pl.BlockSpec((1, L), lambda r, s: (0, r)),
                  pl.BlockSpec((K, L), lambda r, s: (0, r)),
                  pl.BlockSpec((slots, K, L), lambda r, s: (s, 0, r)),
                  pl.BlockSpec((K, K, L), lambda r, s: (0, 0, r))],
        out_specs=pl.BlockSpec((K, L), lambda r, s: (0, r)),
        scratch_shapes=[pltpu.VMEM((K, L), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(n_live.reshape(1, R_pad), J, w, fetch)
    return out[:, :R]
