"""Half-perimeter wirelength (HPWL) cost kernels for the fabric placer.

The annealing placer in :mod:`repro.fabric.place` scores candidate
placements by total HPWL over all nets.  Nets are lowered once to a padded
pin matrix (``net_pins``: net x pin -> entity index, ``net_mask`` marking
real pins); a placement is then just a gather + masked min/max reduction —
the hot numeric loop of PnR, and embarrassingly parallel across annealing
chains.

Full-recompute implementations:

* :func:`hpwl` — jax.numpy, ``jax.jit``-compiled, differentiable-free hot
  path used inside the annealing loop;
* :func:`hpwl_batched` — vmapped over a leading chain axis;
* :func:`hpwl_pallas` — Pallas kernel over the padded per-net coordinate
  matrices, one block of :data:`HPWL_BLOCK_NETS` nets per grid step
  (interpret mode on CPU hosts; compiles for TPU VMEM tiles).

Delta (incremental) implementations — a swap move touches only the nets
incident to the two swapped entities, so the annealer's hot loop rescopes
those ≤2K nets instead of all N:

* :func:`hpwl_delta` — jnp path: gather only the touched nets' pins under
  the candidate permutation and rescore them;
* :func:`hpwl_delta_pallas` — fused Pallas variant: pre-swap pin
  coordinates go to VMEM and the kernel *applies the swap in-kernel*
  (select on the two swapped entity ids) before reducing the per-net
  bounding boxes, emitting new per-net costs plus the move delta.

Fixed-terminal ("mixed") variants — the hierarchical placer's detailed
level anneals each cluster in its own local coordinate frame, with pins
outside the cluster frozen at their estimated positions.  Rather than
materializing those terminals as entities, each net carries a precomputed
*fixed bounding box* (``net_fix``: xmin/xmax/ymin/ymax over its external
pins, rebased into the cluster frame) that is folded into the per-net
reduction:

* :func:`net_hpwl_fixed` / :func:`hpwl_fixed` — full recompute with the
  fixed boxes folded in;
* :func:`hpwl_delta_fixed` — the incremental counterpart of
  :func:`hpwl_delta`;
* :data:`EMPTY_BOX` — the "no external pins" sentinel (min > max, so the
  box never widens a bound and a box-only net scores 0).

A pure-NumPy oracle (:func:`hpwl_reference`) anchors the tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .ops import default_interpret

_BIG = 1e9


def hpwl_reference(pos: np.ndarray, net_pins: np.ndarray,
                   net_mask: np.ndarray) -> float:
    """Pure-Python/NumPy oracle.  pos: (E, 2); net_pins/net_mask: (N, D)."""
    total = 0.0
    for i in range(net_pins.shape[0]):
        xs, ys = [], []
        for j in range(net_pins.shape[1]):
            if net_mask[i, j]:
                e = int(net_pins[i, j])
                xs.append(float(pos[e, 0]))
                ys.append(float(pos[e, 1]))
        if xs:
            total += (max(xs) - min(xs)) + (max(ys) - min(ys))
    return total


def net_hpwl_from_xy(xy: jax.Array, net_mask: jax.Array) -> jax.Array:
    """Per-net HPWL from already-gathered pin coordinates.
    xy: (N, D, 2) float; net_mask: (N, D) bool.  Returns (N,)."""
    x, y = xy[..., 0], xy[..., 1]
    xmin = jnp.min(jnp.where(net_mask, x, _BIG), axis=-1)
    xmax = jnp.max(jnp.where(net_mask, x, -_BIG), axis=-1)
    ymin = jnp.min(jnp.where(net_mask, y, _BIG), axis=-1)
    ymax = jnp.max(jnp.where(net_mask, y, -_BIG), axis=-1)
    valid = jnp.any(net_mask, axis=-1)
    return jnp.where(valid, (xmax - xmin) + (ymax - ymin), 0.0)


def net_hpwl(pos: jax.Array, net_pins: jax.Array,
             net_mask: jax.Array) -> jax.Array:
    """Per-net HPWL.  pos: (E, 2) float; net_pins: (N, D) int (pad entries
    may hold any valid index); net_mask: (N, D) bool.  Returns (N,)."""
    return net_hpwl_from_xy(pos[net_pins], net_mask)


@jax.jit
def hpwl(pos: jax.Array, net_pins: jax.Array,
         net_mask: jax.Array) -> jax.Array:
    """Total HPWL of one placement (scalar)."""
    return jnp.sum(net_hpwl(pos, net_pins, net_mask))


#: (C, E, 2) x (N, D) x (N, D) -> (C,): one HPWL per annealing chain.
hpwl_batched = jax.jit(jax.vmap(hpwl, in_axes=(0, None, None)))


# ---------------------------------------------------------------------------
# Pallas kernel: per-net masked min/max reduction over the pin axis.
# ---------------------------------------------------------------------------
def _hpwl_kernel(x_ref, y_ref, m_ref, o_ref):
    x = x_ref[...]
    y = y_ref[...]
    m = m_ref[...] != 0
    xmin = jnp.min(jnp.where(m, x, _BIG), axis=1, keepdims=True)
    xmax = jnp.max(jnp.where(m, x, -_BIG), axis=1, keepdims=True)
    ymin = jnp.min(jnp.where(m, y, _BIG), axis=1, keepdims=True)
    ymax = jnp.max(jnp.where(m, y, -_BIG), axis=1, keepdims=True)
    valid = jnp.any(m, axis=1, keepdims=True)
    o_ref[...] = jnp.where(valid, (xmax - xmin) + (ymax - ymin), 0.0)


#: nets per grid step of :func:`hpwl_pallas`: bounds the kernel's VMEM
#: footprint (three (block, D) int32/float32 tiles, double-buffered) however
#: many nets the fabric has — all ~14k nets of a 128x128 array at once do
#: not fit in VMEM
HPWL_BLOCK_NETS = 512


@functools.partial(jax.jit, static_argnames=("interpret",))
def hpwl_pallas(pos: jax.Array, net_pins: jax.Array, net_mask: jax.Array,
                *, interpret: Optional[bool] = None) -> jax.Array:
    """Total HPWL via a Pallas reduction kernel.

    Gathers pin coordinates outside the kernel (gathers are host-side
    cheap; the reduction is the VPU-shaped part), pads the pin matrices to
    TPU tile multiples (rows to the net block, pins to 128 lanes), reduces
    one block of nets per grid step, and sums the per-net costs.  On
    integer coordinates the sum is exact, so the result equals
    :func:`hpwl` bit for bit.  ``interpret`` left unset follows
    :func:`~repro.kernels.default_interpret`.
    """
    from .tiling import SUBLANE, pad2d, round_up

    if interpret is None:
        interpret = default_interpret()
    n, d = net_pins.shape
    bn = min(HPWL_BLOCK_NETS, round_up(n, SUBLANE))
    xy = pos[net_pins].astype(jnp.float32)           # (N, D, 2)
    x = pad2d(xy[..., 0], rows=bn)
    y = pad2d(xy[..., 1], rows=bn)
    m = pad2d(net_mask.astype(jnp.int32), rows=bn)
    n_pad, d_pad = m.shape
    block = pl.BlockSpec((bn, d_pad), lambda i: (i, 0))
    per_net = pl.pallas_call(
        _hpwl_kernel,
        grid=(n_pad // bn,),
        in_specs=[block, block, block],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(x, y, m)
    return jnp.sum(per_net)


# ---------------------------------------------------------------------------
# Delta rescoring: only the nets touched by a swap move.
# ---------------------------------------------------------------------------
def _touched_view(net_pins: jax.Array, net_mask: jax.Array,
                  per_net_cost: jax.Array, touched: jax.Array):
    """(pins, mask, old) restricted to the touched nets.

    ``touched`` holds net indices padded with ``N`` (out of range) for
    unused / duplicate entries; those rows come back fully masked with an
    old cost of 0, so they drop out of every reduction.
    """
    n = net_pins.shape[0]
    valid = touched < n
    tc = jnp.minimum(touched, n - 1)
    pins = net_pins[tc]                               # (T, D)
    mask = net_mask[tc] & valid[:, None]
    old = jnp.where(valid, per_net_cost[tc], 0.0)
    return pins, mask, old


def hpwl_delta(slot_xy: jax.Array, cand_slot_of: jax.Array,
               net_pins: jax.Array, net_mask: jax.Array,
               per_net_cost: jax.Array, touched: jax.Array):
    """Rescore only the ``touched`` nets under a candidate permutation.

    slot_xy: (E, 2) slot coordinates; cand_slot_of: (E,) candidate
    entity -> slot permutation; per_net_cost: (N,) current per-net HPWL;
    touched: (T,) int32 net indices (pad/duplicate entries hold N).

    Returns ``(new_vals, delta)``: ``new_vals[t]`` is the candidate HPWL
    of net ``touched[t]`` (0 for padding) and ``delta`` the scalar move
    cost change.  O(T * D) instead of O(N * D).
    """
    pins, mask, old = _touched_view(net_pins, net_mask, per_net_cost,
                                    touched)
    xy = slot_xy[cand_slot_of[pins]]                  # (T, D, 2)
    new_vals = net_hpwl_from_xy(xy, mask)
    return new_vals, jnp.sum(new_vals - old)


def _hpwl_delta_kernel(x_ref, y_ref, p_ref, m_ref, old_ref, ab_ref, sw_ref,
                       new_ref, delta_ref):
    """Fused swap + bounding-box reduction.

    x/y hold the *pre-swap* pin coordinates; ab the two swapped entity
    ids; sw their *post-swap* (x, y) positions.  The swap is applied
    in-kernel (two selects on the resident coordinate tiles), then the
    per-net boxes reduce as in :func:`_hpwl_kernel`.
    """
    p = p_ref[...]
    a, b = ab_ref[0, 0], ab_ref[0, 1]
    x = x_ref[...]
    y = y_ref[...]
    x = jnp.where(p == a, sw_ref[0, 0], jnp.where(p == b, sw_ref[1, 0], x))
    y = jnp.where(p == a, sw_ref[0, 1], jnp.where(p == b, sw_ref[1, 1], y))
    m = m_ref[...] != 0
    xmin = jnp.min(jnp.where(m, x, _BIG), axis=1, keepdims=True)
    xmax = jnp.max(jnp.where(m, x, -_BIG), axis=1, keepdims=True)
    ymin = jnp.min(jnp.where(m, y, _BIG), axis=1, keepdims=True)
    ymax = jnp.max(jnp.where(m, y, -_BIG), axis=1, keepdims=True)
    valid = jnp.any(m, axis=1, keepdims=True)
    new = jnp.where(valid, (xmax - xmin) + (ymax - ymin), 0.0)
    new_ref[...] = new
    delta_ref[...] = jnp.sum(new - old_ref[...], keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hpwl_delta_pallas(slot_xy: jax.Array, slot_of: jax.Array,
                      net_pins: jax.Array, net_mask: jax.Array,
                      per_net_cost: jax.Array, touched: jax.Array,
                      ent_a: jax.Array, ent_b: jax.Array,
                      *, interpret: Optional[bool] = None):
    """Same contract as :func:`hpwl_delta`, but scores *the swap of
    ent_a/ent_b applied to slot_of* without materializing the candidate
    permutation: the touched nets' pre-swap coordinates stay resident in
    VMEM and the kernel applies the swap before reducing.
    """
    from .tiling import pad2d, round_up, SUBLANE

    if interpret is None:
        interpret = default_interpret()
    pins, mask, old = _touched_view(net_pins, net_mask, per_net_cost,
                                    touched)
    t = pins.shape[0]
    t_pad = round_up(t, SUBLANE)
    xy = slot_xy[slot_of[pins]].astype(jnp.float32)   # pre-swap coords
    x = pad2d(xy[..., 0])
    y = pad2d(xy[..., 1])
    p = pad2d(pins.astype(jnp.int32), fill=-1)        # -1 never matches
    m = pad2d(mask.astype(jnp.int32))
    old_p = jnp.zeros((t_pad, 1), jnp.float32).at[:t, 0].set(old)
    ab = jnp.stack([ent_a, ent_b]).astype(jnp.int32)[None]        # (1, 2)
    # post-swap positions: each entity lands on the other's slot
    sw = jnp.stack([slot_xy[slot_of[ent_b]],
                    slot_xy[slot_of[ent_a]]]).astype(jnp.float32)  # (2, 2)
    new_p, delta = pl.pallas_call(
        _hpwl_delta_kernel,
        out_shape=(jax.ShapeDtypeStruct((t_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        interpret=interpret,
    )(x, y, p, m, old_p, ab, sw)
    return new_p[:t, 0], delta[0, 0]


# ---------------------------------------------------------------------------
# Fixed-terminal variants: per-net fixed bounding boxes folded into the
# reduction (cluster-local frames for the hierarchical placer).
# ---------------------------------------------------------------------------

#: per-net "no external pins" box: [xmin, xmax, ymin, ymax] with min > max,
#: the identity of the fold below — jnp.minimum(x, _BIG) == x and
#: jnp.maximum(x, -_BIG) == x exactly, so a sentinel box is a bit-exact
#: no-op and fixed-box programs agree with the plain ones on box-free nets
EMPTY_BOX = (_BIG, -_BIG, _BIG, -_BIG)


def fixed_box(points) -> np.ndarray:
    """[xmin, xmax, ymin, ymax] float32 over (x, y) pairs; EMPTY_BOX when
    there are none.  Host-side helper for lowering cluster-local nets."""
    pts = np.asarray(list(points), np.float32)
    if pts.size == 0:
        return np.asarray(EMPTY_BOX, np.float32)
    return np.asarray([pts[:, 0].min(), pts[:, 0].max(),
                       pts[:, 1].min(), pts[:, 1].max()], np.float32)


def net_hpwl_fixed_from_xy(xy: jax.Array, net_mask: jax.Array,
                           net_fix: jax.Array) -> jax.Array:
    """Per-net HPWL with per-net fixed boxes folded in.
    xy: (N, D, 2); net_mask: (N, D) bool; net_fix: (N, 4).  Returns (N,).
    A net is scored when it has movable pins or a non-empty box."""
    x, y = xy[..., 0], xy[..., 1]
    xmin = jnp.minimum(jnp.min(jnp.where(net_mask, x, _BIG), axis=-1),
                       net_fix[..., 0])
    xmax = jnp.maximum(jnp.max(jnp.where(net_mask, x, -_BIG), axis=-1),
                       net_fix[..., 1])
    ymin = jnp.minimum(jnp.min(jnp.where(net_mask, y, _BIG), axis=-1),
                       net_fix[..., 2])
    ymax = jnp.maximum(jnp.max(jnp.where(net_mask, y, -_BIG), axis=-1),
                       net_fix[..., 3])
    valid = (jnp.any(net_mask, axis=-1)
             | (net_fix[..., 0] <= net_fix[..., 1]))
    return jnp.where(valid, (xmax - xmin) + (ymax - ymin), 0.0)


def net_hpwl_fixed(pos: jax.Array, net_pins: jax.Array, net_mask: jax.Array,
                   net_fix: jax.Array) -> jax.Array:
    """Per-net HPWL under fixed boxes.  Same contract as :func:`net_hpwl`
    plus ``net_fix`` (N, 4)."""
    return net_hpwl_fixed_from_xy(pos[net_pins], net_mask, net_fix)


@jax.jit
def hpwl_fixed(pos: jax.Array, net_pins: jax.Array, net_mask: jax.Array,
               net_fix: jax.Array) -> jax.Array:
    """Total HPWL of one placement with fixed terminals (scalar)."""
    return jnp.sum(net_hpwl_fixed(pos, net_pins, net_mask, net_fix))


def hpwl_delta_fixed(slot_xy: jax.Array, cand_slot_of: jax.Array,
                     net_pins: jax.Array, net_mask: jax.Array,
                     per_net_cost: jax.Array, touched: jax.Array,
                     net_fix: jax.Array):
    """Rescore the ``touched`` nets under fixed boxes — the incremental
    counterpart of :func:`hpwl_delta`, same contract plus ``net_fix``."""
    pins, mask, old = _touched_view(net_pins, net_mask, per_net_cost,
                                    touched)
    n = net_pins.shape[0]
    tc = jnp.minimum(touched, n - 1)
    # pad/duplicate rows are fully masked with old=0; their clamped gather
    # would still pull net n-1's real box, so force those boxes empty too
    fix = jnp.where((touched < n)[:, None], net_fix[tc],
                    jnp.asarray(EMPTY_BOX, net_fix.dtype))
    xy = slot_xy[cand_slot_of[pins]]                  # (T, D, 2)
    new_vals = net_hpwl_fixed_from_xy(xy, mask, fix)
    return new_vals, jnp.sum(new_vals - old)
