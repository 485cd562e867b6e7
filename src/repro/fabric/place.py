"""Simulated-annealing placer with JAX-batched parallel chains.

Follows the cgra_pnr (thunder/SADetailedPlacer) shape: a placement is a
permutation of cells over tiles, moves swap a random cell with a random
tile (occupied -> swap, empty -> move), and candidate states are scored by
total half-perimeter wirelength.  Two engines share one lowering:

* ``backend="python"`` — the classic single-chain annealer with incremental
  per-net cost updates (the reference path);
* ``backend="jax"`` — C independent chains annealed in lockstep, one
  ``lax.fori_loop`` step proposing one move per chain and scoring it with
  the HPWL kernels (:mod:`repro.kernels.pnr_cost`).  On accelerators the
  whole sweep stays on-device.

Move scoring (``score_mode``): a swap touches only the nets incident to
the two swapped entities, so the default ``"delta"`` mode carries the
per-net cost vector through the loop state and rescores just those ≤2K
nets per move (O(K·D) instead of O(N·D)); ``"full"`` recomputes every
net's HPWL per move and is kept as the debug fallback.  Both modes see
identical move schedules and — HPWL values being exactly-representable
integers — compute bit-identical costs, so they accept/reject the same
moves and return bit-identical placements for equal seeds.

PE cells live on the rows x cols grid, I/O cells on the perimeter ring;
moves never cross the two classes, so every intermediate state is legal by
construction.
"""

from __future__ import annotations

import functools
import math
import random as _random
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..kernels.tiling import pow2_bucket as _bucket
from .arch import Coord, FabricSpec
from .netlist import Netlist

__all__ = ["PlacementProblem", "Placement", "HierPlacement", "lower",
           "net_incidence", "anneal_python", "anneal_jax",
           "anneal_jax_batch", "place", "place_hierarchical",
           "batch_signature"]


@dataclass
class PlacementProblem:
    spec: FabricSpec
    cell_names: List[str]            # PE cells first, then I/O cells
    n_pe_cells: int
    n_io_cells: int
    slot_xy: np.ndarray              # (E, 2) float32; PE slots then I/O slots
    n_pe_slots: int
    n_io_slots: int
    net_pins: np.ndarray             # (N, D) int32 entity indices (0-padded)
    net_mask: np.ndarray             # (N, D) bool
    ent_nets: np.ndarray = None      # (E, K) int32 entity -> incident nets,
    # padded with N (out of range) — the incidence table delta scoring uses
    # to find the nets a swap touches
    net_fix: Optional[np.ndarray] = None   # (N, 4) float32 per-net fixed
    # bounding boxes [xmin, xmax, ymin, ymax] over pins *outside* this
    # problem (the hierarchical placer's cluster-local sub-problems);
    # None for ordinary whole-fabric problems

    @property
    def n_entities(self) -> int:
        return self.n_pe_slots + self.n_io_slots

    def entity_of(self, cell_idx: int) -> int:
        """Entity index of the cell_idx-th cell in cell_names order."""
        if cell_idx < self.n_pe_cells:
            return cell_idx
        return self.n_pe_slots + (cell_idx - self.n_pe_cells)


@dataclass
class Placement:
    coords: Dict[str, Coord]         # cell name -> tile
    cost: float                      # HPWL of the chosen chain
    backend: str
    chains: int
    sweeps: int
    chain_costs: List[float] = field(default_factory=list)


def lower(netlist: Netlist, spec: FabricSpec) -> PlacementProblem:
    """Lower a netlist to the padded arrays both annealers consume."""
    pe = sorted(netlist.pe_cells, key=lambda c: c.instance)
    io = sorted(netlist.io_cells, key=lambda c: c.name)
    if len(pe) > spec.n_pe_tiles:
        raise ValueError(f"{len(pe)} PE cells exceed {spec.n_pe_tiles} tiles "
                         f"({spec.summary()}); use spec.fit()")
    if len(io) > spec.n_io_sites:
        raise ValueError(f"{len(io)} I/O cells exceed {spec.n_io_sites} "
                         f"perimeter sites ({spec.summary()})")
    slot_xy = np.asarray(spec.pe_tiles() + spec.io_sites(), np.float32)
    ent_of: Dict[str, int] = {}
    for i, c in enumerate(pe):
        ent_of[c.name] = i
    for j, c in enumerate(io):
        ent_of[c.name] = spec.n_pe_tiles + j

    nets = netlist.nets
    deg = max((n.degree for n in nets), default=1)
    net_pins = np.zeros((max(1, len(nets)), deg), np.int32)
    net_mask = np.zeros_like(net_pins, dtype=bool)
    for i, n in enumerate(nets):
        for j, cell in enumerate([n.driver] + n.sinks):
            net_pins[i, j] = ent_of[cell]
            net_mask[i, j] = True

    return PlacementProblem(
        spec=spec,
        cell_names=[c.name for c in pe] + [c.name for c in io],
        n_pe_cells=len(pe), n_io_cells=len(io),
        slot_xy=slot_xy,
        n_pe_slots=spec.n_pe_tiles, n_io_slots=spec.n_io_sites,
        net_pins=net_pins, net_mask=net_mask,
        ent_nets=net_incidence(net_pins, net_mask,
                               spec.n_pe_tiles + spec.n_io_sites))


def net_incidence(net_pins: np.ndarray, net_mask: np.ndarray,
                  n_entities: int) -> np.ndarray:
    """Padded entity -> incident-nets table for delta move scoring.

    Returns (E, K) int32 where K is the max nets on any entity; unused
    entries hold N (one past the last net) so out-of-range gathers and
    ``mode="drop"`` scatters ignore them.
    """
    n_nets = net_pins.shape[0]
    incident: List[List[int]] = [[] for _ in range(n_entities)]
    for i in range(n_nets):
        for e in net_pins[i][net_mask[i]]:
            incident[int(e)].append(i)
    k = max(1, max((len(l) for l in incident), default=1))
    table = np.full((n_entities, k), n_nets, np.int32)
    for e, l in enumerate(incident):
        table[e, :len(l)] = l
    return table


def _init_slots(p: PlacementProblem, rng: _random.Random) -> np.ndarray:
    """Random legal permutation: entity -> slot, classes kept separate."""
    pe_slots = list(range(p.n_pe_slots))
    io_slots = list(range(p.n_pe_slots, p.n_entities))
    rng.shuffle(pe_slots)
    rng.shuffle(io_slots)
    return np.asarray(pe_slots + io_slots, np.int32)


def _default_t0(p: PlacementProblem) -> float:
    return 0.5 * (p.spec.rows + p.spec.cols)


# ---------------------------------------------------------------------------
# Python reference chain (incremental delta evaluation)
# ---------------------------------------------------------------------------
def anneal_python(p: PlacementProblem, *, seed: int = 0, sweeps: int = 48,
                  t0: Optional[float] = None, t1: float = 0.02
                  ) -> Tuple[np.ndarray, float]:
    """Single annealing chain; returns (slot_of_entity, final HPWL)."""
    rng = _random.Random(seed)
    slot_of = _init_slots(p, rng)
    # maintained inverse permutation: occupant lookup is O(1) per move
    # instead of an O(E) nonzero scan
    ent_at_slot = np.empty_like(slot_of)
    ent_at_slot[slot_of] = np.arange(slot_of.shape[0], dtype=slot_of.dtype)
    pins = p.net_pins
    mask = p.net_mask
    xy = p.slot_xy

    def net_cost(i: int) -> float:
        xs = xy[slot_of[pins[i][mask[i]]]]
        if xs.size == 0:
            return 0.0
        return float(xs[:, 0].max() - xs[:, 0].min()
                     + xs[:, 1].max() - xs[:, 1].min())

    nets_of_ent: Dict[int, List[int]] = {}
    for i in range(pins.shape[0]):
        for e in pins[i][mask[i]]:
            nets_of_ent.setdefault(int(e), []).append(i)
    net_costs = [net_cost(i) for i in range(pins.shape[0])]
    cur = sum(net_costs)
    best = cur
    best_slot = slot_of.copy()

    movable: List[Tuple[int, int, int]] = []      # (lo_ent, n_cells, n_slots)
    if p.n_pe_cells:
        movable.append((0, p.n_pe_cells, p.n_pe_slots))
    if p.n_io_cells:
        movable.append((p.n_pe_slots, p.n_io_cells, p.n_io_slots))
    if not movable:
        return slot_of, 0.0
    n_real = p.n_pe_cells + p.n_io_cells
    steps = max(1, sweeps * n_real)
    t0 = _default_t0(p) if t0 is None else t0

    for step in range(steps):
        lo, n_cells, n_slots = movable[0] if (
            len(movable) == 1 or rng.random() < p.n_pe_cells / n_real
        ) else movable[-1]
        a = lo + rng.randrange(n_cells)
        slot_lo = 0 if lo == 0 else p.n_pe_slots
        t = slot_lo + rng.randrange(n_slots)
        b = int(ent_at_slot[t])
        if a == b:
            continue
        touched = sorted(set(nets_of_ent.get(a, []) + nets_of_ent.get(b, [])))
        old = sum(net_costs[i] for i in touched)
        slot_of[a], slot_of[b] = slot_of[b], slot_of[a]
        new_costs = {i: net_cost(i) for i in touched}
        delta = sum(new_costs.values()) - old
        temp = t0 * (t1 / t0) ** (step / steps)
        if delta <= 0 or rng.random() < math.exp(-delta / max(temp, 1e-9)):
            ent_at_slot[slot_of[a]], ent_at_slot[slot_of[b]] = a, b
            for i, c in new_costs.items():
                net_costs[i] = c
            cur += delta
            if cur < best:
                best, best_slot = cur, slot_of.copy()
        else:
            slot_of[a], slot_of[b] = slot_of[b], slot_of[a]
    return best_slot, float(best)


# ---------------------------------------------------------------------------
# JAX batched chains
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _build_annealer(steps: int, n_pe_c: int, n_io_c: int,
                    n_pe_s: int, n_io_s: int, t0: float, t1: float,
                    hpwl_backend: str = "jnp", score_mode: str = "delta"):
    """Compile one batched annealer per static problem shape.

    Caching here (rather than a fresh ``jax.jit`` per call) is what makes a
    DSE sweep cheap: every variant of the same fabric reuses the program.

    hpwl_backend selects the move-scoring kernel family: ``"jnp"`` (jitted
    jax.numpy reductions) or ``"pallas"`` (the Pallas kernels from
    :mod:`repro.kernels.pnr_cost`, compiled on TPU and interpreted on CPU,
    as :func:`repro.kernels.default_interpret` decides).  score_mode
    selects full recompute (``"full"``, O(N·D) per move) or incremental
    rescoring of only the touched nets (``"delta"``, O(K·D) per move).  All
    four combinations compute identical HPWL, so chains accept identical
    move sequences and return identical placements.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.pnr_cost import (hpwl, hpwl_delta, hpwl_delta_pallas,
                                    hpwl_pallas, net_hpwl)

    if hpwl_backend == "pallas":
        score = hpwl_pallas
    elif hpwl_backend == "jnp":
        score = hpwl
    else:
        raise ValueError(f"unknown hpwl_backend {hpwl_backend!r}")
    if score_mode not in ("delta", "full"):
        raise ValueError(f"unknown score_mode {score_mode!r}")

    n_real = n_pe_c + n_io_c
    p_pe = n_pe_c / n_real
    temps = t0 * (t1 / t0) ** (jnp.arange(steps, dtype=jnp.float32) / steps)

    def chain(key, slot_of0, slot_xy, net_pins, net_mask, ent_nets):
        n_nets = net_pins.shape[0]

        # draw the whole move schedule up front: one RNG call per stream
        # instead of several threefry hashes inside every loop step
        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        pick_pe = jax.random.uniform(k1, (steps,)) < p_pe
        a = jnp.where(pick_pe,
                      jax.random.randint(k2, (steps,), 0, max(1, n_pe_c)),
                      n_pe_s + jax.random.randint(k3, (steps,), 0,
                                                  max(1, n_io_c)))
        t = jnp.where(pick_pe,
                      jax.random.randint(k4, (steps,), 0, n_pe_s),
                      n_pe_s + jax.random.randint(k5, (steps,), 0, n_io_s))
        log_u = jnp.log(jax.random.uniform(k6, (steps,), minval=1e-12))

        def accept_and_track(i, accept, cand, new, state_rest):
            slot_of, cur, best_slot, best = state_rest
            slot_of = jnp.where(accept, cand, slot_of)
            cur = jnp.where(accept, new, cur)
            improved = cur < best
            best_slot = jnp.where(improved, slot_of, best_slot)
            best = jnp.where(improved, cur, best)
            return slot_of, cur, best_slot, best

        if score_mode == "full":
            def cost(slot_of):
                return score(slot_xy[slot_of], net_pins, net_mask)

            def step(i, state):
                slot_of, cur, best_slot, best = state
                ai, ti = a[i], t[i]
                b = jnp.argmax(slot_of == ti)   # occupant of target slot
                cand = slot_of.at[ai].set(slot_of[b]).at[b].set(slot_of[ai])
                new = cost(cand)
                accept = (new <= cur) | (log_u[i] * temps[i] < cur - new)
                return accept_and_track(i, accept, cand, new, state)

            c0 = cost(slot_of0)
            _, _, best_slot, best = jax.lax.fori_loop(
                0, steps, step, (slot_of0, c0, slot_of0, c0))
            return best_slot, best

        # -- delta mode: per-net cost vector rides in the loop state -------
        k2_ = ent_nets.shape[1] * 2
        dup_tri = jnp.tril(jnp.ones((k2_, k2_), bool), k=-1)

        def step(i, state):
            slot_of, pnc, cur, best_slot, best = state
            ai, ti = a[i], t[i]
            b = jnp.argmax(slot_of == ti)       # occupant of target slot
            cand = slot_of.at[ai].set(slot_of[b]).at[b].set(slot_of[ai])
            # nets incident to either swapped entity, deduped so a net
            # touching both contributes its delta exactly once
            tn = jnp.concatenate([ent_nets[ai], ent_nets[b]])
            dup = jnp.any((tn[:, None] == tn[None, :]) & dup_tri, axis=1)
            tn = jnp.where(dup, n_nets, tn)
            if hpwl_backend == "pallas":
                new_vals, delta = hpwl_delta_pallas(
                    slot_xy, slot_of, net_pins, net_mask, pnc, tn,
                    ai, b)
            else:
                new_vals, delta = hpwl_delta(slot_xy, cand, net_pins,
                                             net_mask, pnc, tn)
            new = cur + delta
            accept = (new <= cur) | (log_u[i] * temps[i] < cur - new)
            pnc = jnp.where(accept,
                            pnc.at[tn].set(new_vals, mode="drop"), pnc)
            slot_of, cur, best_slot, best = accept_and_track(
                i, accept, cand, new, (slot_of, cur, best_slot, best))
            return slot_of, pnc, cur, best_slot, best

        pnc0 = net_hpwl(slot_xy[slot_of0], net_pins, net_mask)
        c0 = jnp.sum(pnc0)
        _, _, _, best_slot, best = jax.lax.fori_loop(
            0, steps, step, (slot_of0, pnc0, c0, slot_of0, c0))
        return best_slot, best

    return jax.jit(jax.vmap(chain, in_axes=(0, 0, None, None, None, None)))


def anneal_jax(p: PlacementProblem, *, chains: int = 32, seed: int = 0,
               sweeps: int = 48, t0: Optional[float] = None,
               t1: float = 0.02, hpwl_backend: str = "jnp",
               score_mode: str = "delta"
               ) -> Tuple[np.ndarray, np.ndarray]:
    """C independent chains; returns (slot_of (C, E), costs (C,))."""
    import jax

    n_real = p.n_pe_cells + p.n_io_cells
    if n_real == 0:
        e = np.tile(np.arange(p.n_entities, dtype=np.int32), (chains, 1))
        return e, np.zeros((chains,), np.float32)
    steps = max(1, sweeps * n_real)
    t0 = _default_t0(p) if t0 is None else t0

    run = _build_annealer(steps, p.n_pe_cells, p.n_io_cells,
                          p.n_pe_slots, p.n_io_slots, float(t0), float(t1),
                          hpwl_backend, score_mode)
    rng = _random.Random(seed)
    init = np.stack([_init_slots(p, rng) for _ in range(chains)])
    keys = jax.random.split(jax.random.PRNGKey(seed), chains)
    ent_nets = p.ent_nets if p.ent_nets is not None else net_incidence(
        p.net_pins, p.net_mask, p.n_entities)
    slots, costs = run(keys, init, p.slot_xy, p.net_pins, p.net_mask,
                       ent_nets)
    return np.asarray(slots), np.asarray(costs)


# ---------------------------------------------------------------------------
# Cross-problem batching: many (variant, app) placements in one dispatch
# ---------------------------------------------------------------------------


def batch_signature(p: PlacementProblem, sweeps: int) -> Tuple[int, ...]:
    """Static shape key two problems must share to ride one dispatch."""
    steps = max(1, sweeps * (p.n_pe_cells + p.n_io_cells))
    return (_bucket(steps), _bucket(p.net_pins.shape[0]),
            _bucket(p.net_pins.shape[1]), _bucket(p.n_entities),
            _bucket(p.ent_nets.shape[1]))


#: cost-curve snapshot points captured per chain when telemetry is on
CURVE_POINTS = 16


@functools.lru_cache(maxsize=64)
def _build_batch_annealer(s_pad: int, n_pad: int, d_pad: int, e_pad: int,
                          k_pad: int, t1: float, hpwl_backend: str,
                          score_mode: str, telemetry: bool = False,
                          fixed: bool = False):
    """One compiled chain program for every problem of one bucket signature.

    Unlike :func:`_build_annealer` (which bakes the cell/slot counts into
    the program as static Python ints), the batched chain takes them as
    *data* — so PE1 on camera and PE4 on conv can share a program as long
    as their padded shapes land in the same buckets.  Moves are sampled by
    scaling uniforms with the dynamic counts, the temperature schedule uses
    the dynamic per-problem step count, and steps beyond a problem's real
    budget are masked to rejects.

    With ``telemetry`` the chain additionally returns its accepted-move
    count and :data:`CURVE_POINTS` current-cost snapshots.  The telemetry
    state only *observes* the accept decision and running cost — the move
    schedule and cost arithmetic are untouched — so placements and costs
    are bit-identical to the untelemetered program.

    With ``fixed`` the chain additionally takes a per-net fixed-box array
    (``net_fix``, (N, 4)) and scores through the ``*_fixed`` kernels — the
    hierarchical placer's cluster-local sub-problems, whose external pins
    are frozen boxes rather than entities.  Sentinel (:data:`EMPTY_BOX`)
    rows make the fixed fold a bit-exact no-op, so box-free nets score
    identically to the plain program.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.pnr_cost import (hpwl, hpwl_delta, hpwl_delta_fixed,
                                    hpwl_fixed, net_hpwl, net_hpwl_fixed)

    if hpwl_backend != "jnp":
        raise ValueError("anneal_jax_batch supports hpwl_backend='jnp' only "
                         "(the pallas delta kernel scores one swap per call)")
    if score_mode not in ("delta", "full"):
        raise ValueError(f"unknown score_mode {score_mode!r}")

    def chain(key, slot_of0, slot_xy, net_pins, net_mask, ent_nets,
              dims, t0, net_fix=None):
        if fixed:
            def total_cost(pos):
                return hpwl_fixed(pos, net_pins, net_mask, net_fix)

            def per_net_cost(pos):
                return net_hpwl_fixed(pos, net_pins, net_mask, net_fix)

            def delta_cost(cand, pnc, tn):
                return hpwl_delta_fixed(slot_xy, cand, net_pins, net_mask,
                                        pnc, tn, net_fix)
        else:
            def total_cost(pos):
                return hpwl(pos, net_pins, net_mask)

            def per_net_cost(pos):
                return net_hpwl(pos, net_pins, net_mask)

            def delta_cost(cand, pnc, tn):
                return hpwl_delta(slot_xy, cand, net_pins, net_mask,
                                  pnc, tn)
        n_pe_c, n_io_c, n_pe_s, n_io_s, n_steps = (
            dims[0], dims[1], dims[2], dims[3], dims[4])
        n_real = jnp.maximum(n_pe_c + n_io_c, 1)

        k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
        pick_pe = (jax.random.uniform(k1, (s_pad,))
                   < n_pe_c.astype(jnp.float32) / n_real.astype(jnp.float32))

        def scaled(k, count, lo):
            u = jax.random.uniform(k, (s_pad,))
            idx = jnp.minimum((u * count).astype(jnp.int32),
                              jnp.maximum(count - 1, 0))
            return lo + idx

        a = jnp.where(pick_pe, scaled(k2, n_pe_c, 0),
                      scaled(k3, n_io_c, n_pe_s))
        t = jnp.where(pick_pe, scaled(k4, n_pe_s, 0),
                      scaled(k5, n_io_s, n_pe_s))
        log_u = jnp.log(jax.random.uniform(k6, (s_pad,), minval=1e-12))
        frac = (jnp.arange(s_pad, dtype=jnp.float32)
                / jnp.maximum(n_steps.astype(jnp.float32), 1.0))
        temps = t0 * (t1 / t0) ** frac
        active = jnp.arange(s_pad) < n_steps

        def tele0():
            return (jnp.int32(0), jnp.zeros((CURVE_POINTS,), jnp.float32))

        def tele_track(i, accept, cur, tele):
            n_acc, curve = tele
            n_acc = n_acc + accept.astype(jnp.int32)
            idx = jnp.minimum((i * CURVE_POINTS) // s_pad, CURVE_POINTS - 1)
            return n_acc, curve.at[idx].set(cur)

        def accept_and_track(accept, cand, new, state_rest):
            slot_of, cur, best_slot, best = state_rest
            slot_of = jnp.where(accept, cand, slot_of)
            cur = jnp.where(accept, new, cur)
            improved = cur < best
            best_slot = jnp.where(improved, slot_of, best_slot)
            best = jnp.where(improved, cur, best)
            return slot_of, cur, best_slot, best

        if score_mode == "full":
            def step(i, state):
                slot_of, cur, best_slot, best = state[:4]
                ai, ti = a[i], t[i]
                b = jnp.argmax(slot_of == ti)
                cand = slot_of.at[ai].set(slot_of[b]).at[b].set(slot_of[ai])
                new = total_cost(slot_xy[cand])
                accept = ((new <= cur)
                          | (log_u[i] * temps[i] < cur - new)) & active[i]
                out = accept_and_track(accept, cand, new, state[:4])
                if telemetry:
                    return out + tele_track(i, accept, out[1], state[4:])
                return out

            c0 = total_cost(slot_xy[slot_of0])
            state0 = (slot_of0, c0, slot_of0, c0)
            if telemetry:
                state0 = state0 + tele0()
            out = jax.lax.fori_loop(0, s_pad, step, state0)
            if telemetry:
                return out[2], out[3], out[4], out[5]
            return out[2], out[3]

        k2_ = k_pad * 2
        dup_tri = jnp.tril(jnp.ones((k2_, k2_), bool), k=-1)

        def step(i, state):
            slot_of, pnc, cur, best_slot, best = state[:5]
            ai, ti = a[i], t[i]
            b = jnp.argmax(slot_of == ti)
            cand = slot_of.at[ai].set(slot_of[b]).at[b].set(slot_of[ai])
            tn = jnp.concatenate([ent_nets[ai], ent_nets[b]])
            dup = jnp.any((tn[:, None] == tn[None, :]) & dup_tri, axis=1)
            tn = jnp.where(dup, n_pad, tn)
            new_vals, delta = delta_cost(cand, pnc, tn)
            new = cur + delta
            accept = ((new <= cur)
                      | (log_u[i] * temps[i] < cur - new)) & active[i]
            pnc = jnp.where(accept,
                            pnc.at[tn].set(new_vals, mode="drop"), pnc)
            slot_of, cur, best_slot, best = accept_and_track(
                accept, cand, new, (slot_of, cur, best_slot, best))
            if telemetry:
                tele = tele_track(i, accept, cur, state[5:])
                return (slot_of, pnc, cur, best_slot, best) + tele
            return slot_of, pnc, cur, best_slot, best

        pnc0 = per_net_cost(slot_xy[slot_of0])
        c0 = jnp.sum(pnc0)
        state0 = (slot_of0, pnc0, c0, slot_of0, c0)
        if telemetry:
            state0 = state0 + tele0()
        out = jax.lax.fori_loop(0, s_pad, step, state0)
        if telemetry:
            return out[3], out[4], out[5], out[6]
        return out[3], out[4]

    # one flat vmap over problems x chains, each row carrying its own
    # problem data: a nested vmap (outer problems, inner chains with the
    # problem arrays broadcast) would avoid the per-chain copies but
    # measures ~2x slower end to end on the Fig. 11 suite, so the copies
    # (a few MB at these sizes) buy the better-vectorizing flat batch
    return jax.jit(jax.vmap(chain))


def check_anneal_budget(p: PlacementProblem, chains: int, sweeps: int,
                        max_states: Optional[int], *,
                        metrics=None) -> None:
    """Refuse (pre-dispatch) an anneal whose state count exceeds budget.

    The annealing budget is deterministic and size-based — ``chains x
    sweeps x n_entities`` proposed states per problem — so exhaustion is
    a property of the problem, not of wall clock, and results stay
    bit-identical whenever the budget is *not* exhausted.  Raises
    :class:`repro.errors.BudgetExceeded` before any compilation or
    dispatch happens; no-op when ``max_states`` is None (the default).
    """
    if max_states is None:
        return
    states = chains * max(1, sweeps * (p.n_pe_cells + p.n_io_cells))
    if states > max_states:
        if metrics is not None:
            metrics.inc("pnr.budget_exhausted")
        from ..errors import BudgetExceeded
        raise BudgetExceeded(
            f"anneal needs {states} states "
            f"({chains} chains x {sweeps} sweeps x "
            f"{p.n_pe_cells + p.n_io_cells} cells > "
            f"anneal_max_states={max_states})",
            states=states, max_states=max_states, chains=chains,
            sweeps=sweeps, n_entities=p.n_entities)


def anneal_jax_batch(problems: List[PlacementProblem], *, chains: int = 16,
                     seed: int = 0, sweeps: int = 32,
                     t0: Optional[float] = None, t1: float = 0.02,
                     score_mode: str = "delta",
                     nonces: Optional[List[int]] = None,
                     telemetry: Optional[bool] = None,
                     metrics=None, max_states: Optional[int] = None
                     ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Anneal many placement problems in one JAX dispatch.

    All problems must share one :func:`batch_signature`; every problem's
    arrays are padded to the signature's bucket shapes (masked nets score
    zero, dummy entities sit on dummy slots and are never proposed as
    moves) and all ``len(problems) x chains`` chains run as one vmapped
    ``fori_loop``.  Returns per problem ``(slot_of (C, E), costs (C,))``
    with E the problem's real entity count — the same contract as
    :func:`anneal_jax`.

    Each problem's chains draw from ``fold_in(PRNGKey(seed), nonce)`` with
    ``nonces[i]`` defaulting to ``i``.  Callers wanting placements that are
    reproducible *regardless of grouping* (the explore pipeline's memo
    contract) pass a content-derived nonce per problem; with bucket-shape
    padding the result then depends only on the problem itself, never on
    its groupmates.

    ``telemetry`` (default: :func:`repro.obs.telemetry_enabled`) selects a
    compiled variant that also reports per-chain accept counts and
    cost-curve snapshots; placements stay bit-identical.  Acceptance rates
    land in ``metrics`` (histogram ``pnr.anneal.accept_rate``, cost curves
    as ``pnr.anneal.cost_curve.<nonce>`` gauges), defaulting to the global
    registry.
    """
    import jax

    from ..kernels.pnr_cost import EMPTY_BOX
    from ..obs import telemetry_enabled
    from ..obs.metrics import global_registry

    if telemetry is None:
        telemetry = telemetry_enabled()

    if nonces is None:
        nonces = list(range(len(problems)))
    if len(nonces) != len(problems):
        raise ValueError("nonces must match problems 1:1")
    for p in problems:
        check_anneal_budget(p, chains, sweeps, max_states,
                            metrics=metrics or global_registry())
    sigs = {batch_signature(p, sweeps) for p in problems}
    if len(sigs) != 1:
        raise ValueError(f"problems span {len(sigs)} batch signatures; "
                         f"group by batch_signature() first")
    s_pad, n_pad, d_pad, e_pad, k_pad = next(iter(sigs))

    n_p = len(problems)
    has_fix = any(p.net_fix is not None for p in problems)
    net_pins = np.zeros((n_p, n_pad, d_pad), np.int32)
    net_mask = np.zeros((n_p, n_pad, d_pad), bool)
    net_fix = (np.tile(np.asarray(EMPTY_BOX, np.float32), (n_p, n_pad, 1))
               if has_fix else None)
    slot_xy = np.zeros((n_p, e_pad, 2), np.float32)
    ent_nets = np.full((n_p, e_pad, k_pad), n_pad, np.int32)
    dims = np.zeros((n_p, 5), np.int32)
    t0s = np.zeros((n_p,), np.float32)
    init = np.tile(np.arange(e_pad, dtype=np.int32), (n_p, chains, 1))
    keys = np.zeros((n_p, chains, 2), np.uint32)
    base_key = jax.random.PRNGKey(seed)
    for i, p in enumerate(problems):
        n, d = p.net_pins.shape
        net_pins[i, :n, :d] = p.net_pins
        net_mask[i, :n, :d] = p.net_mask
        if p.net_fix is not None:
            net_fix[i, :n] = p.net_fix
        e = p.n_entities
        slot_xy[i, :e] = p.slot_xy
        en = np.where(p.ent_nets == n, n_pad, p.ent_nets)
        ent_nets[i, :e, :en.shape[1]] = en
        n_real = p.n_pe_cells + p.n_io_cells
        dims[i] = (p.n_pe_cells, p.n_io_cells, p.n_pe_slots, p.n_io_slots,
                   max(1, sweeps * n_real))
        t0s[i] = _default_t0(p) if t0 is None else t0
        rng = _random.Random(seed)
        for c in range(chains):
            init[i, c, :e] = _init_slots(p, rng)
        keys[i] = np.asarray(jax.random.split(
            jax.random.fold_in(base_key, nonces[i] & 0x7FFFFFFF), chains))

    run = _build_batch_annealer(s_pad, n_pad, d_pad, e_pad, k_pad,
                                float(t1), "jnp", score_mode,
                                bool(telemetry), has_fix)

    def flat(x):                     # (P, C, ...) -> (P*C, ...)
        return x.reshape((n_p * chains,) + x.shape[2:])

    def tile(x):                     # (P, ...) -> (P*C, ...) per-chain copy
        return np.repeat(x, chains, axis=0)

    args = (flat(keys), flat(init), tile(slot_xy),
            tile(net_pins), tile(net_mask), tile(ent_nets),
            tile(dims), tile(t0s))
    if has_fix:
        args = args + (tile(net_fix),)
    out = run(*args)
    slots = np.asarray(out[0]).reshape(n_p, chains, e_pad)
    costs = np.asarray(out[1]).reshape(n_p, chains)
    if telemetry:
        reg = metrics if metrics is not None else global_registry()
        accepts = np.asarray(out[2]).reshape(n_p, chains)
        curves = np.asarray(out[3]).reshape(n_p, chains, CURVE_POINTS)
        for i, p in enumerate(problems):
            steps_i = max(1, sweeps * (p.n_pe_cells + p.n_io_cells))
            reg.observe("pnr.anneal.accept_rate",
                        float(accepts[i].mean()) / steps_i)
            best_chain = int(np.argmin(costs[i]))
            reg.set_gauge(f"pnr.anneal.cost_curve.{nonces[i] & 0x7FFFFFFF}",
                          [round(float(c), 3) for c in
                           curves[i, best_chain]])
    return [(slots[i, :, :p.n_entities], costs[i])
            for i, p in enumerate(problems)]


def place(netlist: Netlist, spec: FabricSpec, *, backend: str = "jax",
          chains: int = 32, sweeps: int = 48, seed: int = 0,
          t0: Optional[float] = None, t1: float = 0.02,
          hpwl_backend: str = "jnp", score_mode: str = "delta",
          max_states: Optional[int] = None) -> Placement:
    """Anneal and return the best chain's placement.

    ``max_states`` bounds the anneal state budget (chains x sweeps x
    entities) exactly like the batched path — the serial fallback must
    not silently out-spend the budget the grouped dispatch enforces.
    """
    if hpwl_backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown hpwl_backend {hpwl_backend!r}")
    if score_mode not in ("delta", "full"):
        raise ValueError(f"unknown score_mode {score_mode!r}")
    p = lower(netlist, spec)
    check_anneal_budget(p, chains, sweeps, max_states)

    if backend == "python":
        if hpwl_backend != "jnp":
            raise ValueError(
                "hpwl_backend applies to the jax annealer only; the python "
                "reference scores moves without the HPWL kernel")
        # the python reference is inherently incremental; score_mode only
        # selects between the jax engine's two scoring programs
        chain_results = [anneal_python(p, seed=seed + c, sweeps=sweeps,
                                       t0=t0, t1=t1)
                         for c in range(chains)]
        slots = np.stack([s for s, _ in chain_results])
        costs = np.asarray([c for _, c in chain_results], np.float32)
    elif backend == "jax":
        slots, costs = anneal_jax(p, chains=chains, seed=seed, sweeps=sweeps,
                                  t0=t0, t1=t1, hpwl_backend=hpwl_backend,
                                  score_mode=score_mode)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    best = int(np.argmin(costs))
    slot_of = slots[best]
    coords: Dict[str, Coord] = {}
    for idx, name in enumerate(p.cell_names):
        ent = p.entity_of(idx)
        x, y = p.slot_xy[slot_of[ent]]
        coords[name] = (int(x), int(y))
    return Placement(coords=coords, cost=float(costs[best]), backend=backend,
                     chains=chains, sweeps=sweeps,
                     chain_costs=[float(c) for c in costs])


# ---------------------------------------------------------------------------
# Two-level hierarchical placement (cluster -> detail -> deblock)
# ---------------------------------------------------------------------------


@dataclass
class HierPlacement(Placement):
    """A :class:`Placement` plus the hierarchical flow's per-level record.

    The level arrays exist so callers (the pnr benchmark, the tests) can
    assert delta-vs-full bit-identity *per level*, not just on the final
    coordinates: ``cluster_slots`` is the winning coarse chain (cluster ->
    region slot), ``detail_slots[k]`` the winning local chain of cluster
    ``k``, ``deblock_slots`` the winning seam-refinement chain (empty
    when the pass was skipped).  ``cost`` is the *exact* whole-netlist
    HPWL of the final coordinates — the same objective :func:`place`
    reports — while ``level_costs`` holds each level's own (approximate,
    fixed-terminal) objective.
    """

    cluster_grid: int = 1
    clusters: List[List[str]] = field(default_factory=list)
    region_of: Dict[int, Coord] = field(default_factory=dict)
    cluster_slots: Optional[np.ndarray] = None
    detail_slots: Dict[int, np.ndarray] = field(default_factory=dict)
    deblock_slots: Optional[np.ndarray] = None
    level_costs: Dict[str, float] = field(default_factory=dict)
    detail_dispatches: int = 0


def _auto_cluster_grid(spec: FabricSpec) -> int:
    """Largest cluster grid whose regions stay >= 16x16 (>= 8x8 for small
    fabrics) and divide the array evenly; 1 means 'place flat'."""
    for target in (16, 8):
        for g in range(min(spec.rows, spec.cols) // target, 1, -1):
            if spec.rows % g == 0 and spec.cols % g == 0:
                return g
    return 1


def _region_spec(spec: FabricSpec, rh: int, rw: int) -> FabricSpec:
    return FabricSpec(rows=rh, cols=rw, channel_width=spec.channel_width,
                      io_capacity=spec.io_capacity,
                      hop_energy_pj=spec.hop_energy_pj,
                      hop_delay_ns=spec.hop_delay_ns,
                      latch_depth=spec.latch_depth)


def _nets_problem(spec: FabricSpec, cell_names: List[str], n_slots: int,
                  slot_xy: np.ndarray, nets: List[Tuple[List[int], list]]
                  ) -> PlacementProblem:
    """PlacementProblem over one movable PE class with fixed-box nets.

    nets: (entity pins, external fixed points) per net; the points are
    already in the problem's coordinate frame.
    """
    from ..kernels.pnr_cost import EMPTY_BOX, fixed_box

    n = max(1, len(nets))
    d = max(1, max((len(e) for e, _ in nets), default=1))
    net_pins = np.zeros((n, d), np.int32)
    net_mask = np.zeros((n, d), bool)
    net_fix = np.tile(np.asarray(EMPTY_BOX, np.float32), (n, 1))
    for i, (ents, ext) in enumerate(nets):
        net_pins[i, :len(ents)] = ents
        net_mask[i, :len(ents)] = True
        if ext:
            net_fix[i] = fixed_box(ext)
    return PlacementProblem(
        spec=spec, cell_names=list(cell_names),
        n_pe_cells=len(cell_names), n_io_cells=0,
        slot_xy=np.asarray(slot_xy, np.float32),
        n_pe_slots=n_slots, n_io_slots=0,
        net_pins=net_pins, net_mask=net_mask,
        ent_nets=net_incidence(net_pins, net_mask, n_slots),
        net_fix=net_fix)


def place_hierarchical(netlist: Netlist, spec: FabricSpec, *,
                       cluster_grid: Optional[int] = None,
                       chains: int = 16, sweeps: int = 32, seed: int = 0,
                       score_mode: str = "delta",
                       cluster_score_mode: Optional[str] = None,
                       detail_score_mode: Optional[str] = None,
                       deblock_score_mode: Optional[str] = None,
                       cluster_sweeps: Optional[int] = None,
                       deblock_sweeps: Optional[int] = None,
                       deblock_halo: int = 1, deblock_t0: float = 2.0,
                       t1: float = 0.02,
                       max_states: Optional[int] = None,
                       metrics=None) -> HierPlacement:
    """Two-level placement for mega-fabrics (cgra_pnr's cluster ->
    detail -> deblock recipe on top of :func:`anneal_jax_batch`).

    1. **Partition** (:func:`repro.fabric.cluster.partition`): PE cells
       into ``cluster_grid**2`` connectivity-tight clusters, one per
       region of the evenly divided array.
    2. **Cluster level**: the clusters anneal as one small batched
       problem on the ``cluster_grid x cluster_grid`` coarse grid
       (inter-cluster nets only), assigning each cluster a region.
    3. **I/O**: perimeter cells go greedily to the free site nearest the
       centroid of their partner clusters' regions.
    4. **Detail level**: every cluster's cells anneal over its region's
       tiles — all clusters *simultaneously*, grouped by
       :func:`batch_signature` into giant pow2-bucketed vmapped
       dispatches.  External pins enter as per-net fixed boxes in the
       cluster's local frame (:func:`repro.kernels.pnr_cost.hpwl_delta_fixed`).
    5. **Deblock**: cells within ``deblock_halo`` tiles of a region seam
       re-anneal jointly across the seams at low temperature.

    ``score_mode`` selects delta/full move scoring for every level;
    the per-level overrides (``cluster_score_mode`` etc.) pin one level
    only.  Both modes are bit-identical per level at equal seeds (gated
    by ``benchmarks/pnr_bench.py``).  ``cluster_grid=1`` (or an array
    too small for the auto grid) degenerates to the flat single-level
    path and is bit-identical to :func:`place` at equal arguments.
    ``cluster_grid`` must divide rows and cols evenly with regions at
    least 2x2.
    """
    from ..kernels.pnr_cost import hpwl
    from ..obs import span
    from ..obs.metrics import global_registry

    if score_mode not in ("delta", "full"):
        raise ValueError(f"unknown score_mode {score_mode!r}")
    reg = metrics if metrics is not None else global_registry()
    g = _auto_cluster_grid(spec) if cluster_grid is None else int(cluster_grid)
    if g < 1:
        raise ValueError(f"cluster_grid must be >= 1, got {g}")
    if g == 1:
        flat = place(netlist, spec, backend="jax", chains=chains,
                     sweeps=sweeps, seed=seed, score_mode=score_mode,
                     t1=t1, max_states=max_states)
        return HierPlacement(coords=flat.coords, cost=flat.cost,
                             backend=flat.backend, chains=chains,
                             sweeps=sweeps, chain_costs=flat.chain_costs,
                             cluster_grid=1,
                             level_costs={"final_hpwl": flat.cost})
    if spec.rows % g or spec.cols % g:
        raise ValueError(f"cluster_grid {g} must divide rows x cols "
                         f"({spec.rows}x{spec.cols}) evenly")
    rh, rw = spec.rows // g, spec.cols // g
    if rh < 2 or rw < 2:
        raise ValueError(f"cluster_grid {g} leaves {rw}x{rh} regions; "
                         f"regions must be at least 2x2")
    cluster_sweeps = sweeps if cluster_sweeps is None else cluster_sweeps
    deblock_sweeps = (max(1, sweeps // 2) if deblock_sweeps is None
                      else deblock_sweeps)

    from .cluster import partition

    k_total = g * g
    with span("pnr.hier.partition", clusters=k_total):
        clus = partition(netlist, k_total, rh * rw)
    reg.inc("pnr.hier.place")
    total_nets = max(1, clus.cut_nets + clus.internal_nets)
    reg.observe("pnr.hier.cut_frac", clus.cut_nets / total_nets)

    # -- level 1: anneal cluster centroids on the g x g coarse grid --------
    coarse_spec = _region_spec(spec, g, g)
    coarse_nets = []
    for net in netlist.nets:
        ks = sorted({clus.cluster_of[c] for c in [net.driver] + net.sinks
                     if c in clus.cluster_of})
        if len(ks) > 1:
            coarse_nets.append((ks, []))
    coarse = _nets_problem(coarse_spec, [f"c{k}" for k in range(k_total)],
                           k_total, coarse_spec.pe_tiles(), coarse_nets)
    coarse.net_fix = None            # no external pins at the top level
    with span("pnr.hier.cluster", clusters=k_total, nets=len(coarse_nets)):
        (cslots, ccosts), = anneal_jax_batch(
            [coarse], chains=chains, seed=seed, sweeps=cluster_sweeps,
            t1=t1, score_mode=cluster_score_mode or score_mode,
            nonces=[0], metrics=reg, max_states=max_states)
    cbest = int(np.argmin(ccosts))
    cluster_slots = np.asarray(cslots[cbest])
    region_of: Dict[int, Coord] = {}
    origin: Dict[int, Tuple[int, int]] = {}
    center: Dict[int, Tuple[float, float]] = {}
    for k in range(k_total):
        rx, ry = coarse.slot_xy[cluster_slots[k]]
        region_of[k] = (int(rx), int(ry))
        origin[k] = (int(rx) * rw, int(ry) * rh)
        center[k] = (origin[k][0] + (rw - 1) / 2.0,
                     origin[k][1] + (rh - 1) / 2.0)

    # -- I/O cells: nearest free perimeter site to their partners ----------
    coords: Dict[str, Coord] = {}
    io_cells = sorted(netlist.io_cells, key=lambda c: c.name)
    partners: Dict[str, List[int]] = {c.name: [] for c in io_cells}
    for net in netlist.nets:
        pins = [net.driver] + net.sinks
        ks = [clus.cluster_of[c] for c in pins if c in clus.cluster_of]
        for c in pins:
            if c in partners:
                partners[c].extend(ks)
    free = list(enumerate(spec.io_sites()))
    with span("pnr.hier.io", cells=len(io_cells)):
        for c in io_cells:
            ks = partners[c.name]
            if ks:
                ex = sum(center[k][0] for k in ks) / len(ks)
                ey = sum(center[k][1] for k in ks) / len(ks)
            else:
                ex, ey = (spec.cols - 1) / 2.0, (spec.rows - 1) / 2.0
            j = min(range(len(free)),
                    key=lambda j: (abs(free[j][1][0] - ex)
                                   + abs(free[j][1][1] - ey), free[j][0]))
            coords[c.name] = free.pop(j)[1]

    # -- level 2: all clusters' detailed placements, one batched dispatch
    # per bucket signature -------------------------------------------------
    local_ent: Dict[str, int] = {}
    for k in range(k_total):
        for j, name in enumerate(clus.clusters[k]):
            local_ent[name] = j
    cluster_net_lists: List[List[Tuple[List[int], list]]] = [
        [] for _ in range(k_total)]
    for net in netlist.nets:
        by_k: Dict[int, List[int]] = {}
        io_pts = []
        for c in [net.driver] + net.sinks:
            k = clus.cluster_of.get(c)
            if k is None:
                io_pts.append(coords[c])
            else:
                by_k.setdefault(k, []).append(local_ent[c])
        for k, ents in by_k.items():
            ext = [center[j] for j in by_k if j != k] + io_pts
            ox, oy = origin[k]
            cluster_net_lists[k].append(
                (ents, [(px - ox, py - oy) for px, py in ext]))
    region_tiles = [(x, y) for y in range(rh) for x in range(rw)]
    local_spec = _region_spec(spec, rh, rw)
    problems: Dict[int, PlacementProblem] = {}
    for k in range(k_total):
        if clus.clusters[k]:
            problems[k] = _nets_problem(local_spec, clus.clusters[k],
                                        rh * rw, region_tiles,
                                        cluster_net_lists[k])
    groups: Dict[Tuple, List[int]] = {}
    for k in sorted(problems):
        groups.setdefault(batch_signature(problems[k], sweeps), []).append(k)
    detail_slots: Dict[int, np.ndarray] = {}
    detail_cost = 0.0
    with span("pnr.hier.detail", clusters=len(problems),
              dispatches=len(groups)):
        for sig in sorted(groups):
            idxs = groups[sig]
            out = anneal_jax_batch(
                [problems[k] for k in idxs], chains=chains, seed=seed,
                sweeps=sweeps, t1=t1,
                score_mode=detail_score_mode or score_mode,
                nonces=[k + 1 for k in idxs], metrics=reg,
                max_states=max_states)
            reg.observe("pnr.hier.detail_bucket", len(idxs))
            for k, (slots, costs) in zip(idxs, out):
                best = int(np.argmin(costs))
                detail_slots[k] = np.asarray(slots[best])
                detail_cost += float(costs[best])
    for k, prob in problems.items():
        ox, oy = origin[k]
        for j, name in enumerate(prob.cell_names):
            x, y = prob.slot_xy[detail_slots[k][j]]
            coords[name] = (int(x) + ox, int(y) + oy)

    # -- level 3: deblock — re-anneal the seam halo across clusters --------
    xs = {i * rw + dx for i in range(1, g) for dx in range(-deblock_halo,
                                                           deblock_halo)}
    ys = {i * rh + dy for i in range(1, g) for dy in range(-deblock_halo,
                                                           deblock_halo)}
    halo_tiles = [(x, y) for y in range(spec.rows) for x in range(spec.cols)
                  if x in xs or y in ys]
    halo_set = set(halo_tiles)
    pe_cells = sorted(netlist.pe_cells, key=lambda c: c.instance)
    movable = [c.name for c in pe_cells if coords[c.name] in halo_set]
    deblock_slots = None
    if movable and deblock_sweeps > 0:
        ent_of = {name: j for j, name in enumerate(movable)}
        dnets = []
        for net in netlist.nets:
            ents, ext = [], []
            for c in [net.driver] + net.sinks:
                if c in ent_of:
                    ents.append(ent_of[c])
                else:
                    ext.append(coords[c])
            if ents:
                dnets.append((ents, ext))
        dprob = _nets_problem(spec, movable, len(halo_tiles), halo_tiles,
                              dnets)
        tile_slot = {t: s for s, t in enumerate(halo_tiles)}
        incumbent = np.asarray([tile_slot[coords[name]] for name in movable]
                               + list(range(len(movable), len(halo_tiles))),
                               np.int32)
        with span("pnr.hier.deblock", cells=len(movable),
                  tiles=len(halo_tiles)):
            (dslots, dcosts), = anneal_jax_batch(
                [dprob], chains=chains, seed=seed, sweeps=deblock_sweeps,
                t0=deblock_t0, t1=t1,
                score_mode=deblock_score_mode or score_mode,
                nonces=[k_total + 1], metrics=reg, max_states=max_states)
        dbest = int(np.argmin(dcosts))
        # the anneal restarts from random seam permutations; keep the
        # detail-level arrangement when no chain beats it
        from ..kernels.pnr_cost import hpwl_fixed
        incumbent_cost = float(hpwl_fixed(
            dprob.slot_xy[incumbent], dprob.net_pins, dprob.net_mask,
            dprob.net_fix))
        if float(dcosts[dbest]) < incumbent_cost:
            deblock_slots = np.asarray(dslots[dbest])
            deblock_cost = float(dcosts[dbest])
            reg.inc("pnr.hier.deblock_improved")
        else:
            deblock_slots = incumbent
            deblock_cost = incumbent_cost
        for j, name in enumerate(movable):
            x, y = dprob.slot_xy[deblock_slots[j]]
            coords[name] = (int(x), int(y))
    else:
        deblock_cost = 0.0

    # -- exact whole-netlist objective of the final coordinates ------------
    full = lower(netlist, spec)
    slot_index = {t: i for i, t in enumerate(spec.pe_tiles())}
    slot_index.update({t: spec.n_pe_tiles + i
                       for i, t in enumerate(spec.io_sites())})
    slot_of = np.arange(full.n_entities, dtype=np.int32)
    for idx, name in enumerate(full.cell_names):
        slot_of[full.entity_of(idx)] = slot_index[coords[name]]
    final = float(hpwl(full.slot_xy[slot_of], full.net_pins, full.net_mask))
    return HierPlacement(
        coords=coords, cost=final, backend="jax", chains=chains,
        sweeps=sweeps, chain_costs=[], cluster_grid=g,
        clusters=clus.clusters, region_of=region_of,
        cluster_slots=cluster_slots, detail_slots=detail_slots,
        deblock_slots=deblock_slots, detail_dispatches=len(groups),
        level_costs={"cluster": float(ccosts[cbest]),
                     "detail": detail_cost, "deblock": deblock_cost,
                     "final_hpwl": final})
