"""Flash-decoding adapted to TPU/ICI: decode attention over a KV cache that
is sharded along the SEQUENCE dimension across the ``model`` axis.

Why: GQA archs with n_kv_heads < model-axis size (qwen kv=8, llama kv=8,
granite kv=1 on a 16-wide axis) cannot head-shard their caches; replicating
them explodes HBM and the naive GSPMD lowering all-gathers the whole cache
every step (the collective-bound decode cells in the baseline roofline
table).  Sequence-sharding instead gives every rank S/tp cache slots; each
rank computes a partial online-softmax over its slots and the results merge
with one tiny (max, sum, weighted-psum) exchange of [B, H, hd]-sized
statistics — O(B*H*hd) wire bytes instead of O(B*S*Hkv*hd).

The cache write is also local: the rank owning slot ``pos`` does the
dynamic-update-slice; everyone else no-ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def flash_decode_ref(q, k, v, pos):
    """q [B,1,Hq,hd]; k/v [B,S,Hkv,hd]; attend over slots <= pos."""
    b, _, hq, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, hd).astype(jnp.float32) / np.sqrt(hd)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k.astype(jnp.float32))
    mask = jnp.arange(s)[None, None, None, :] <= pos
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(b, 1, hq, hd).astype(q.dtype)


def _local_partial(q, k_loc, v_loc, pos, s_start):
    """Partial flash statistics over one sequence shard."""
    b, _, hq, hd = q.shape
    s_loc, hkv = k_loc.shape[1], k_loc.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, hd).astype(jnp.float32) / np.sqrt(hd)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k_loc.astype(jnp.float32))
    kpos = s_start + jnp.arange(s_loc)
    scores = jnp.where(kpos[None, None, None, :] <= pos, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                          # [b,hkv,g]
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(jnp.isfinite(m)[..., None], p, 0.0)     # all-masked shard
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, v_loc.astype(jnp.float32))
    return m, l, o


def _merge(m, l, o, axis):
    gmax = jax.lax.pmax(m, axis)
    scale = jnp.exp(m - gmax)
    l_g = jax.lax.psum(l * scale, axis)
    o_g = jax.lax.psum(o * scale[..., None], axis)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None]


def flash_decode(q, k, v, pos, mesh: Mesh, axis: str = "model",
                 batch_spec=None):
    """Standalone sequence-sharded decode attention (no cache write).
    q [B,1,Hq,hd]; k/v [B,S,Hkv,hd] (S divisible by mesh.shape[axis])."""
    tp = mesh.shape[axis]
    b, _, hq, hd = q.shape
    s = k.shape[1]
    assert s % tp == 0, (s, tp)

    def local(qb, kb, vb):
        idx = jax.lax.axis_index(axis)
        m, l, o = _local_partial(qb, kb, vb, pos, idx * (s // tp))
        out = _merge(m, l, o, axis)
        return out.reshape(qb.shape).astype(qb.dtype)

    bs = batch_spec
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(bs, None, None, None), P(bs, axis, None, None),
                                 P(bs, axis, None, None)),
                       out_specs=P(bs, None, None, None),
                       check_vma=False)
    return fn(q, k, v)


def flash_decode_update(q, k_new, v_new, k_cache, v_cache, pos, mesh: Mesh,
                        axis: str = "model", batch_spec=None):
    """Cache-updating variant: writes (k_new, v_new) at slot ``pos`` into the
    sequence-sharded caches (local write on the owning rank) and returns
    (attn_out, k_cache, v_cache)."""
    tp = mesh.shape[axis]
    s = k_cache.shape[1]
    assert s % tp == 0
    s_loc = s // tp

    def local(qb, knb, vnb, kcb, vcb):
        idx = jax.lax.axis_index(axis)
        start = idx * s_loc
        off = pos - start
        in_range = (off >= 0) & (off < s_loc)
        off_c = jnp.clip(off, 0, s_loc - 1)
        kw = jax.lax.dynamic_update_slice_in_dim(kcb, knb.astype(kcb.dtype), off_c, 1)
        vw = jax.lax.dynamic_update_slice_in_dim(vcb, vnb.astype(vcb.dtype), off_c, 1)
        kcb = jnp.where(in_range, kw, kcb)
        vcb = jnp.where(in_range, vw, vcb)
        m, l, o = _local_partial(qb, kcb, vcb, pos, start)
        out = _merge(m, l, o, axis).reshape(qb.shape).astype(qb.dtype)
        return out, kcb, vcb

    bs = batch_spec
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(bs, None, None, None), P(bs, None, None, None),
                                 P(bs, None, None, None), P(bs, axis, None, None),
                                 P(bs, axis, None, None)),
                       out_specs=(P(bs, None, None, None), P(bs, axis, None, None),
                                  P(bs, axis, None, None)),
                       check_vma=False)
    return fn(q, k_new, v_new, k_cache, v_cache)
