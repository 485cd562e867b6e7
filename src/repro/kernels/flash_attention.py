"""Flash attention Pallas kernel (GQA, causal, sliding window, softcap).

Blockwise online-softmax attention with q-tiles resident in VMEM — the
HBM-traffic-optimal loop order (contrast with the XLA kv-chunk scan in
models/layers.py, whose full-sequence accumulator round-trips HBM every
chunk; see EXPERIMENTS.md §Perf).

Layouts: q (B, Hq, S, D); k/v (B, Hkv, S, D); grid (B, Hq, S/bq); the kv
block index map folds the GQA group (h -> h // group).  The kv loop runs
over ``ceil(S/bk)`` blocks with causal/window masking via iota comparisons;
fully-masked trailing blocks are skipped by bounding the fori upper limit
with the causal horizon of the q-tile.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                 seq: int, scale: float, causal: bool, window: int,
                 softcap: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_kv = seq // bk
    if causal:
        # highest kv block any row of this q-tile may attend to
        hi = jnp.minimum(((qi + 1) * bq - 1) // bk + 1, n_kv)
    else:
        hi = n_kv

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return acc_new, m_new, l_new

    d = q_ref.shape[-1]
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    lo = 0
    if causal and window:
        lo = jnp.maximum(0, (qi * bq - window) // bk)
    acc, m, l = lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float = 0.0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq % Hkv == 0.

    Returns (B, Hq, S, D) in q.dtype.  S must be a multiple of max(bq, bk)
    (ops.py pads).
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale or (1.0 / math.sqrt(d))
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)

    grid = (b, hq, s // bq)
    kernel = functools.partial(
        _attn_kernel, bq=bq, bk=bk, seq=s, scale=scale, causal=causal,
        window=window, softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda b_, h, i, group=group: (b_, h // group, 0, 0)),
            pl.BlockSpec((1, 1, s, d),
                         lambda b_, h, i, group=group: (b_, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v)
