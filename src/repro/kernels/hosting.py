"""Pallas kernels for the hosting engine's two per-slot hot paths.

1. ``dp_minplus_kc`` — the offline-OPT forward recursion
   (``offline_opt.dp_fwd_chunk``'s scan body) fused over a whole [chunk] of
   slots: the [K] value frontier stays in registers/VMEM across the slot
   loop instead of round-tripping through a ``lax.scan`` carry, and the
   kernel emits the [chunk, K] argmin table for backtracking.  Frontier
   freezing past ``T_len`` (identity argmins on invalid slots) and ``+inf``
   pricing of padded K levels ride in unchanged: invalid slots carry ``J``
   through and write ``iota`` rows, and ``+inf`` entries of ``w``/``fetch``
   propagate through min/argmin exactly as in the XLA reference.

1b. ``dp_minplus_rows`` — the same recursion for many rows at once when
   only the frontier is wanted (the forward passes that keep no argmin
   table): rows on the 128 lanes, the frontier ``[K, 128]`` on sublanes,
   and each row block's ``[K, K, 128]`` fetch matrices resident in VMEM
   for the whole chunk, so a wide joint-state DP reads them from HBM once
   per chunk instead of once per slot.

2. ``slot_uniform_tc`` — the counter-keyed uniform draw of
   ``scenarios.base.slot_uniform`` with the whole threefry2x32 chain
   (``fold_in(key, t)`` -> optional salt fold -> uniform bits) fused into
   one kernel pass per [chunk] of slots, instead of 2-3 vmapped
   ``jax.random`` dispatches per chunk.

The kernels are **bit-identical** to their ``jax.random`` / ``lax.scan``
references — same hash, same u->bits mapping, same float op order — which
is what lets the engine treat backend choice as a pure performance knob
(see the backend-dispatch invariant in ROADMAP.md).  The batched [B] form
of kernels 1 and 2 is ``jax.vmap`` of the per-instance kernel: Pallas
lifts the vmap onto a leading grid axis, so the fleet engine's existing
per-instance vmap is the blocking over [B].  Kernel 1b takes the batch
itself, rows-minor; the engine reaches it through a batching rule
(``offline_opt.dp_fwd_frontier``).

The threefry2x32 implementation below (rotation schedule, key schedule,
counter layout) mirrors jax's; ``tests/test_kernels.py`` pins exact bit
equality against ``jax.random.fold_in`` / ``uniform`` across random keys,
salts and non-aligned chunk sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.utils import default_interpret, pad_to


# ----------------------------------------------------------------------
# threefry2x32 (the jax.random hash), as plain jnp ops: traceable inside a
# Pallas kernel body and usable standalone as an XLA reference.
# ----------------------------------------------------------------------

_ROTS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)


def threefry2x32(k0, k1, x0, x1):
    """One threefry2x32 block: hash counter words ``(x0, x1)`` under key
    ``(k0, k1)``; all args uint32 arrays (broadcastable).  Bit-identical to
    jax's ``threefry2x32`` primitive — 20 rounds, 5 key injections."""
    k2 = k0 ^ k1 ^ _PARITY
    ks = (k0, k1, k2)
    x0 = x0 + k0
    x1 = x1 + k1
    for r in range(5):
        for rot in _ROTS[r % 2]:
            x0 = x0 + x1
            x1 = (x1 << rot) | (x1 >> (32 - rot))
            x1 = x0 ^ x1
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + np.uint32(r + 1)
    return x0, x1


def threefry_fold(k0, k1, d):
    """``jax.random.fold_in((k0, k1), d)`` on raw uint32 words: hash the
    fold data as a 1-word counter; the output pair is the folded key."""
    return threefry2x32(k0, k1, jnp.zeros_like(d), d)


def uniform_from_bits(bits):
    """jax's uint32 -> U(0,1) float32 mapping: splice the top 23 random
    bits into a [1, 2) float, subtract 1.  The trailing ``maximum`` mirrors
    ``jax.random.uniform``'s clamp op-for-op (a bitwise no-op here since
    the result is already >= 0)."""
    fb = (bits >> np.uint32(9)) | np.uint32(0x3F800000)
    u = jax.lax.bitcast_convert_type(fb, jnp.float32) - np.float32(1.0)
    return jnp.maximum(np.float32(0.0), u)


# ----------------------------------------------------------------------
# Kernel 1: fused DP min-plus forward chunk.
# ----------------------------------------------------------------------

def _dp_minplus_kernel(n_live_ref, j_ref, w_ref, f_ref, jout_ref, args_ref,
                       w_s, args_s, *, chunk: int, K: int):
    fetch = f_ref[...]                            # [K, K], VMEM-resident
    n_live = n_live_ref[0, 0]                     # SMEM scalar
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    w_s[...] = jnp.transpose(w_ref[...])          # [K, chunk] -> [chunk, K]

    def body(t, J):                               # J [1, K] frontier row
        # the exact op order of dp_fwd_chunk's scan body — where/add order
        # matters for bits; the argmin is spelled out as "lowest k_prev
        # holding the min", jnp.argmin's first-occurrence tie rule
        trans = jnp.transpose(J) + fetch          # [K_prev, K_next]
        m = jnp.min(trans, axis=0, keepdims=True)
        arg = jnp.min(jnp.where(trans == m, rows, K), axis=0, keepdims=True)
        Jn = m + w_s[pl.ds(t, 1), :]
        v = jnp.full((1, K), t, jnp.int32) < n_live
        args_s[pl.ds(t, 1), :] = jnp.where(v, arg, iota)
        return jnp.where(v, Jn, J)

    jout_ref[...] = jax.lax.fori_loop(0, chunk, body, j_ref[...])
    args_ref[...] = jnp.transpose(args_s[...])


def dp_minplus_kc(J, wck, fetch_mat, n_live, *, interpret=None):
    """One instance, one chunk of the DP forward recursion.

    Args: ``J`` [K] float32 entry frontier; ``wck`` [chunk, K] float32
    per-slot holding costs (``+inf`` on masked levels); ``fetch_mat``
    [K, K] float32; ``n_live`` int32 scalar — slots ``t < n_live`` are
    live, the rest frozen (``T_len - t0`` for the chunk's contiguous
    counters ``t0 + arange(chunk)``).
    Returns ``(J' [K], args [chunk, K] int32)`` — bit-identical to the
    ``lax.scan`` body in ``offline_opt.dp_fwd_chunk``.

    Batched use is ``jax.vmap`` over a leading [B] axis (Pallas turns that
    into the batch grid dimension).  Every per-instance operand is at
    least 2-D with its trailing dims whole (``J`` as a ``[1, K]`` row,
    ``n_live`` a ``[1, 1]`` SMEM scalar), which keeps the batched blocks
    legal for Mosaic.  The two tables cross HBM slot-minor (``[K, chunk]``),
    so K pads to 8 sublanes rather than 128 lanes — at B = chunk = 4096 the
    slot-major form would need 8.6 GB per table.  The kernel transposes
    them to and from ``[chunk, K]`` VMEM scratch once per chunk, which
    bounds ``chunk`` by VMEM (about 1 KiB per slot).
    """
    chunk, K = wck.shape
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(_dp_minplus_kernel, chunk=chunk, K=K)
    vmem = pl.BlockSpec()
    Jout, args_t = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((1, K), jnp.float32),
                   jax.ShapeDtypeStruct((K, chunk), jnp.int32)],
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem],
        scratch_shapes=[pltpu.VMEM((chunk, K), jnp.float32),
                        pltpu.VMEM((chunk, K), jnp.int32)],
        interpret=interpret,
    )(jnp.asarray(n_live, jnp.int32).reshape(1, 1),
      J.astype(jnp.float32).reshape(1, K), wck.astype(jnp.float32).T,
      fetch_mat.astype(jnp.float32))
    return Jout[0], args_t.T


# ----------------------------------------------------------------------
# Kernel 1b: row-blocked, frontier-only DP forward (rows on lanes).
# ----------------------------------------------------------------------

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


# ----------------------------------------------------------------------
# Kernel 2: fused counter-keyed uniform generation.
# ----------------------------------------------------------------------

def _slot_uniform_kernel(key_ref, t_ref, u_ref, *, salt, partitionable):
    k0 = key_ref[0, 0]                            # SMEM scalars
    k1 = key_ref[0, 1]
    t = t_ref[...].astype(jnp.uint32)
    z = jnp.zeros_like(t)
    a0, a1 = threefry2x32(k0, k1, z, t)           # fold_in(key, t)
    if salt is not None:
        a0, a1 = threefry2x32(a0, a1, z, jnp.full_like(t, np.uint32(salt)))
    b0, b1 = threefry2x32(a0, a1, z, z)           # random_bits(key, 32, ())
    # jax's two random_bits layouts: the partitionable one (the default
    # since jax 0.5) xors both output words, the original keeps word 0
    bits = b0 ^ b1 if partitionable else b0
    u_ref[...] = uniform_from_bits(bits)


def slot_uniform_tc(key, tids, salt=None, *, interpret=None):
    """One instance, one chunk of counter-keyed U(0,1) draws.

    Args: ``key`` raw uint32 [2] PRNG key; ``tids`` [chunk] int32 global
    slot counters; ``salt`` optional *static* int sub-stream fold.
    Returns [chunk] float32 — bit-identical to
    ``scenarios.base.slot_uniform``'s vmapped ``fold_in`` + ``uniform``
    chain.  Batched use is ``jax.vmap`` over [B, 2] keys (and optionally
    [B, chunk] counters): the key rides as a ``[1, 2]`` SMEM operand and
    the counters as one ``[1, chunk]`` row, so the batched blocks keep
    whole trailing dims.
    """
    chunk = tids.shape[0]
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(
        _slot_uniform_kernel, salt=None if salt is None else int(salt),
        partitionable=bool(jax.config.jax_threefry_partitionable))
    u = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, chunk), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), pl.BlockSpec()],
        interpret=interpret,
    )(jnp.asarray(key, jnp.uint32).reshape(1, 2),
      jnp.asarray(tids, jnp.int32).reshape(1, chunk))
    return u[0]
