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

2. ``slot_uniform_tc`` — the counter-keyed uniform draw of
   ``scenarios.base.slot_uniform`` with the whole threefry2x32 chain
   (``fold_in(key, t)`` -> optional salt fold -> uniform bits) fused into
   one kernel pass per [chunk] of slots, instead of 2-3 vmapped
   ``jax.random`` dispatches per chunk.

Both kernels are **bit-identical** to their ``jax.random`` / ``lax.scan``
references — same hash, same u->bits mapping, same float op order — which
is what lets the engine treat backend choice as a pure performance knob
(see the backend-dispatch invariant in ROADMAP.md).  The batched [B] form
is ``jax.vmap`` of the per-instance kernel: Pallas lifts the vmap onto a
leading grid axis, so the fleet engine's existing per-instance vmap is the
blocking over [B].

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

from repro.kernels.utils import default_interpret


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
