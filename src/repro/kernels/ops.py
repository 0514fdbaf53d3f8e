"""Jitted public wrappers around the model Pallas kernels (flash attention,
the SSD scan): layout handling, padding to block multiples, and dtype
plumbing.  ``interpret=None`` (the default) resolves through
``kernels.utils.default_interpret``: the Pallas interpreter on CPU, the
compiled Mosaic kernel on TPU.

Padding/block-fitting arithmetic lives in ``kernels.utils`` (one shared
copy, also used by the hosting kernel ``hosting.dp_minplus_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_bhcqd
from repro.kernels.utils import default_interpret, fit_block, pad_to as _pad_to


@functools.partial(jax.jit, static_argnames=("causal", "q_offset", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, q_offset: int = 0,
                    bq: int = 128, bk: int = 128, interpret=None):
    """q [B,S,Hq,hd]; k/v [B,Skv,Hkv,hd] -> [B,S,Hq,hd]."""
    if interpret is None:
        interpret = default_interpret()
    b, sq, hq, hd = q.shape
    qb = jnp.moveaxis(q, 2, 1)                    # [B,H,S,hd]
    kb = jnp.moveaxis(k, 2, 1)
    vb = jnp.moveaxis(v, 2, 1)
    bq = fit_block(bq, sq)
    bk = fit_block(bk, k.shape[1])
    qb, pq = _pad_to(qb, 2, bq)
    kb, pk = _pad_to(kb, 2, bk)
    vb, _ = _pad_to(vb, 2, bk)
    out = flash_attention_bhsd(qb, kb, vb, causal=causal, q_offset=q_offset,
                               bq=bq, bk=bk, interpret=interpret)
    out = out[:, :, :sq]
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, h0=None, chunk: int = 128, interpret=None):
    """Mamba2 SSD. x [b,s,nh,dh]; dt [b,s,nh]; A [nh]; B/C [b,s,ng,ds];
    h0 [b,nh,dh,ds] or None.  Returns (y [b,s,nh,dh], hT)."""
    if interpret is None:
        interpret = default_interpret()
    b, s, nh, dh = x.shape
    ng, ds = B.shape[2], B.shape[3]
    q = fit_block(chunk, s)
    xp, pad = _pad_to(x, 1, q)
    dtp, _ = _pad_to(dt, 1, q)         # padded dt=0 -> decay 1, input 0: no-op
    Bp, _ = _pad_to(B, 1, q)
    Cp, _ = _pad_to(C, 1, q)
    nc = xp.shape[1] // q
    xr = jnp.moveaxis(xp.reshape(b, nc, q, nh, dh), 3, 1)     # [b,nh,nc,q,dh]
    dtr = jnp.moveaxis(dtp.reshape(b, nc, q, nh), 3, 1)       # [b,nh,nc,q]
    Br = jnp.moveaxis(Bp.reshape(b, nc, q, ng, ds), 3, 1)     # [b,ng,nc,q,ds]
    Cr = jnp.moveaxis(Cp.reshape(b, nc, q, ng, ds), 3, 1)
    if h0 is None:
        h0 = jnp.zeros((b, nh, dh, ds), jnp.float32)
    y, hT = ssd_scan_bhcqd(xr, dtr, A.astype(jnp.float32), Br, Cr,
                           h0.astype(jnp.float32), interpret=interpret)
    y = jnp.moveaxis(y, 1, 3).reshape(b, nc * q, nh, dh)[:, :s]
    return y, hT
