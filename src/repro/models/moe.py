"""Mixture-of-Experts layer with row-local capacity dispatch.

Production MoE under GSPMD needs the dispatch combinatorics (sort /
position-in-expert / scatter) to stay *local to each data shard* — a global
argsort over all tokens forces the partitioner to gather the whole token
stream onto every device (observed: ~PB-scale all-reduce per step).  The
trick: do the dispatch per *sequence* (batched over the leading B axis that
is sharded over ``data``):

1. router top-k per token;
2. per-row counting sort: position-in-expert via a cumulative one-hot count
   along the row, capacity per (row, expert) = ceil(S*k/E * cf) — tokens
   beyond capacity are dropped (static shapes, standard practice);
3. batched scatter into a (B, E, C, d) buffer — B sharded over ``data``,
   E over ``model`` (expert parallelism), so the scatter is shard-local and
   the only cross-device movement is the B x E resharding all-to-all that
   GSPMD inserts at the expert-compute boundary;
4. batched expert SwiGLU einsum over (B, E, C, d);
5. per-row gather-combine weighted by router probs;
6. optional dense shared experts (qwen2-moe).

No (T, E, C) one-hot dispatch tensor is ever materialized.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import MoEConfig


def router_topk(x: jax.Array, w_router: jax.Array, moe: MoEConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (weights (B,S,k), expert ids (B,S,k))."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    e_pad = w_router.shape[1]
    if e_pad > moe.n_experts:                      # mask padded experts
        pad = jnp.full((1, 1, e_pad - moe.n_experts), -1e30, logits.dtype)
        logits = jnp.concatenate(
            [logits[..., :moe.n_experts],
             jnp.broadcast_to(pad, logits.shape[:2] + (e_pad - moe.n_experts,))],
            axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = lax.top_k(probs, moe.top_k)        # (B,S,k)
    if moe.router_norm_topk:
        vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    return vals, idx


def moe_mlp_shardmap(x: jax.Array, params: Dict[str, jax.Array],
                     moe: MoEConfig, mesh, bp_axes) -> jax.Array:
    """Explicit expert parallelism under shard_map (§Perf hillclimb).

    GSPMD's partitioning of the pjit dispatch replicates the (B, E, C, d)
    buffer across `model` and pays an O(E x C x d) f32 all-reduce in the
    backward pass (observed: the dominant collective for qwen3).  Here each
    model-rank dispatches its *local* tokens to its E/16 *local* experts —
    all combinatorics (top-k, counting sort, scatter) are rank-local and
    sized E_loc — and the only collective is a psum of the partial token
    outputs over `model` (plus its identity-cost transpose in backward):
    per layer ~|activations| bytes instead of ~|dispatch buffer| bytes.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    e_pad = params["w_router"].shape[1]
    n_model = mesh.shape["model"]
    assert e_pad % n_model == 0, (e_pad, n_model)
    e_loc = e_pad // n_model
    k = moe.top_k

    def local_fn(x_l, wr, wg, wu, wd):
        b_l, s, d = x_l.shape
        t = b_l * s
        xt = x_l.reshape(t, d)
        weights, experts = router_topk(x_l, wr, moe)       # (B_l,S,k)
        flat_e = experts.reshape(t * k)
        flat_w = weights.reshape(t * k)
        flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

        rank = lax.axis_index("model")
        local_e = flat_e - rank * e_loc
        mine = (local_e >= 0) & (local_e < e_loc)
        local_e_c = jnp.where(mine, local_e, 0)
        # position within each local expert: exclusive running count
        oh = (local_e_c[:, None] == jnp.arange(e_loc)[None]) & mine[:, None]
        oh = oh.astype(jnp.int32)
        pos_all = jnp.cumsum(oh, axis=0) - oh
        pos = jnp.take_along_axis(pos_all, local_e_c[:, None],
                                  axis=1)[:, 0]
        capacity = int(max(k, round(t * k / moe.n_experts
                                    * moe.capacity_factor)))
        keep = mine & (pos < capacity)

        gathered = jnp.where(keep[:, None], xt[flat_t], 0).astype(x_l.dtype)
        buf = jnp.zeros((e_loc, capacity, d), x_l.dtype)
        buf = buf.at[local_e_c, jnp.where(keep, pos, capacity)].set(
            gathered, mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = (jax.nn.silu(g) * u).astype(x_l.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)

        part = out_buf[local_e_c, jnp.where(keep, pos, 0)]
        part = part.astype(jnp.float32) * (flat_w * keep)[:, None]
        y = jnp.zeros((t, d), jnp.float32).at[flat_t].add(part)
        y = lax.psum(y, "model")
        return y.reshape(b_l, s, d).astype(x_l.dtype)

    bp = P(bp_axes, None, None)
    y = shard_map(
        local_fn, mesh=mesh,
        in_specs=(bp, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=bp,
        check_vma=False,
    )(x, params["w_router"], params["wg"], params["wu"], params["wd"])

    # shared experts stay on the plain pjit path (dense, replicated weights)
    if moe.n_shared and "sg" in params:
        sg = jnp.einsum("bsd,df->bsf", x, params["sg"])
        su = jnp.einsum("bsd,df->bsf", x, params["su"])
        shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(sg) * su,
                            params["sd"])
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,d->bs", x.astype(jnp.float32),
            params["shared_gate"].astype(jnp.float32)))
        y = y + (shared.astype(jnp.float32)
                 * gate[..., None]).astype(y.dtype)
    return y


def moe_mlp(x: jax.Array, params: Dict[str, jax.Array], moe: MoEConfig,
            shard=lambda x, name: x) -> jax.Array:
    """x: (B, S, d).  params:
      w_router (d, E_pad); wg/wu (E_pad, d, d_expert); wd (E_pad, d_expert, d)
      optional shared experts: sg/su (d, d_shared), sd (d_shared, d),
      shared_gate (d,)

    `shard` pins the (B, E, C, d) buffers to (data, model, -, -): without the
    constraint GSPMD un-shards B for the expert einsum, replicating expert
    compute across the whole data axis (observed 16x flops).
    """
    b, s, d = x.shape
    e_pad = params["w_router"].shape[1]
    k = moe.top_k
    sk = s * k

    weights, experts = router_topk(x, params["w_router"], moe)   # (B,S,k)

    # ---- row-local dispatch ------------------------------------------------
    flat_e = experts.reshape(b, sk)                                # (B, S*k)
    flat_w = weights.reshape(b, sk)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None], (b, sk))

    # position of each (token, choice) within its expert's row-local queue:
    # count same-expert entries strictly before it along the row.
    onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)        # (B,S*k,E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot                  # exclusive
    pos = jnp.take_along_axis(pos_all, flat_e[..., None],
                              axis=-1)[..., 0]                     # (B, S*k)

    capacity = int(max(k, round(sk / moe.n_experts * moe.capacity_factor)))
    keep = pos < capacity

    xt = x                                                          # (B,S,d)
    gathered = jnp.take_along_axis(
        xt, flat_t[..., None], axis=1)                              # (B,S*k,d)
    gathered = jnp.where(keep[..., None], gathered, 0).astype(x.dtype)

    rows = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None], (b, sk))
    buf = jnp.zeros((b, e_pad, capacity, d), x.dtype)
    buf = buf.at[rows, flat_e, jnp.where(keep, pos, capacity)].set(
        gathered, mode="drop")                                      # (B,E,C,d)
    buf = shard(buf, "moe_buf")

    # ---- expert compute (E sharded over `model` => expert parallel) --------
    g = jnp.einsum("becd,edf->becf", buf, params["wg"])
    u = jnp.einsum("becd,edf->becf", buf, params["wu"])
    h = shard((jax.nn.silu(g) * u).astype(x.dtype), "moe_h")
    out_buf = jnp.einsum("becf,efd->becd", h, params["wd"]).astype(x.dtype)
    from .perf_flags import get_flags
    if get_flags().moe_combine == "sharded":
        # keep expert outputs E-sharded; the combine gather pays a forward
        # all-gather but the backward stays sharded (§Perf hillclimb)
        out_buf = shard(out_buf, "moe_h")
    else:
        out_buf = shard(out_buf, "moe_buf")       # replicate E (baseline)

    # ---- combine -------------------------------------------------------------
    expert_out = out_buf[rows, flat_e, jnp.where(keep, pos, 0)]    # (B,S*k,d)
    expert_out = expert_out * (flat_w * keep).astype(jnp.float32)[..., None]
    y = jnp.zeros((b, s, d), jnp.float32)
    y = y.at[rows, flat_t].add(expert_out.astype(jnp.float32))

    # ---- shared experts (qwen2-moe) --------------------------------------------
    if moe.n_shared and "sg" in params:
        sg = jnp.einsum("bsd,df->bsf", x, params["sg"])
        su = jnp.einsum("bsd,df->bsf", x, params["su"])
        shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(sg) * su,
                            params["sd"])
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,d->bs", x.astype(jnp.float32),
            params["shared_gate"].astype(jnp.float32)))
        y = y + shared.astype(jnp.float32) * gate[..., None]

    return y.astype(x.dtype)
