"""Expert-parallel MoE dispatch under shard_map.

Layout at entry: activations x [B, S, D] sharded over the batch axes and
*replicated* over ``model``; expert weights [E, D, F] sharded over ``model``
(E_loc = E/tp experts per rank).  Because every model rank already holds its
data-row's tokens, dispatch needs **no token exchange at all**: each rank
gathers the tokens routed to its local experts (a local sort), runs its
expert GEMMs, scatters contributions back, and a single psum over ``model``
combines — the same one all-reduce a dense TP MLP pays.  The global-sort
collective pathology of naive GSPMD dispatch disappears.

(An all-to-all variant for fully token-sharded activations is the documented
next step in EXPERIMENTS.md §Perf; this gather+psum scheme is what the
baseline lowers.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding.context import ShardCtx


def _local_expert_pass(x_loc, w, ids, w_gate, w_in, w_out, expert_mask_loc,
                       e0, e_total, capacity):
    """x_loc [N, D]; w/ids [N, k]; w_* [E_loc, ...]. Returns partial y [N, D]
    containing only the local experts' contributions."""
    n, d = x_loc.shape
    k = ids.shape[1]
    e_loc = w_in.shape[0]
    nk = n * k

    mine = (ids >= e0) & (ids < e0 + e_loc)
    le = jnp.where(mine, ids - e0, e_loc)            # e_loc = trash bucket
    le_flat = le.reshape(nk)
    tok_flat = jnp.repeat(jnp.arange(n), k)
    w_flat = w.reshape(nk)

    order = jnp.argsort(le_flat)
    se = le_flat[order]
    st = tok_flat[order]
    sw = w_flat[order]

    counts = jnp.bincount(se, length=e_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(nk) - starts[se]
    keep = (se < e_loc) & (pos < capacity)
    pos_c = jnp.where(keep, pos, capacity - 1)
    se_c = jnp.where(keep, se, 0)

    buf = jnp.zeros((e_loc, capacity, d), x_loc.dtype)
    src = jnp.where(keep[:, None], x_loc[st], 0.0)
    buf = buf.at[se_c, pos_c].add(src)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_in)
    out = jnp.einsum("ecf,efd->ecd", h, w_out)
    if expert_mask_loc is not None:
        out = out * expert_mask_loc[:, None, None].astype(out.dtype)

    gathered = out[se_c, pos_c]
    contrib = jnp.where(keep[:, None], gathered * sw[:, None].astype(out.dtype), 0.0)
    return jnp.zeros((n, d), out.dtype).at[st].add(contrib)


def moe_shardmap_apply(ctx: ShardCtx, x, w, ids, w_gate, w_in, w_out,
                       expert_mask, capacity_factor: float):
    """x [B, S, D] (batch sharded over ctx.batch_axes, replicated over model);
    w/ids [B, S, k]; expert weights [E, D, F] sharded over model on E."""
    b, s, d = x.shape
    k = ids.shape[-1]
    e_total = w_in.shape[0]
    tp = ctx.tp
    n_loc = (b // ctx.dp) * s
    capacity = int(np.ceil(n_loc * k * capacity_factor / e_total))
    capacity = max(capacity, k, 8)
    baxes = ctx.batch_axes if len(ctx.batch_axes) != 1 else ctx.batch_axes[0]
    bspec = baxes if ctx.batch_axes else None
    ma = ctx.model_axis

    def local_fn(x_l, w_l, ids_l, wg_l, wi_l, wo_l, mask_l):
        bl, sl = x_l.shape[0], x_l.shape[1]
        m = jax.lax.axis_index(ma)
        e0 = m * (e_total // tp)
        y = _local_expert_pass(x_l.reshape(bl * sl, d), w_l.reshape(-1, k),
                               ids_l.reshape(-1, k), wg_l, wi_l, wo_l,
                               mask_l, e0, e_total, capacity)
        y = jax.lax.psum(y, ma)
        return y.reshape(bl, sl, d)

    mask_arg = expert_mask if expert_mask is not None else jnp.ones((e_total,), jnp.float32)
    mask_spec = P(ma)

    fn = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None), P(bspec, None, None),
                  P(ma, None, None), P(ma, None, None), P(ma, None, None),
                  mask_spec),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )
    return fn(x, w, ids, w_gate, w_in, w_out,
              mask_arg if expert_mask is not None else mask_arg)
