"""Faults planted under the timed path, for the checks that ``correct``
must fail (``tests/test_correct.py`` and ``readings.py --fault``).

Each plant replaces one of the program's functions for the life of a run
and returns the function that puts it back:

``anneal_unchanged``  the batched annealer returns its chains' initial
                      random placements, each reported at its true HPWL
``half_dropped``      a served response carries the records of only half
                      of its pairs
``route_altered``     the router's first net loses its last channel
``sim_altered``       the batched stepper's first output is off by one
``record_altered``    a served record reports one cycle more of II than
                      its simulation ran at
"""

from __future__ import annotations

import dataclasses
import importlib
import random
from typing import Callable, Dict

import numpy as np


def _anneal_unchanged() -> Callable[[], None]:
    place = importlib.import_module("repro.fabric.place")

    def unchanged(problems, *, chains=16, seed=0, **_):
        out = []
        for p in problems:
            rng = random.Random(seed)
            slots = np.stack([place._init_slots(p, rng)
                              for _ in range(chains)])
            costs = []
            for s in slots:
                xy = p.slot_xy[s]
                total = 0.0
                for pins, mask in zip(p.net_pins, p.net_mask):
                    q = xy[pins[mask]]
                    total += float(np.ptp(q[:, 0]) + np.ptp(q[:, 1]))
                costs.append(total)
            out.append((slots, np.asarray(costs, np.float32)))
        return out

    orig = place.anneal_jax_batch
    place.anneal_jax_batch = unchanged
    return lambda: setattr(place, "anneal_jax_batch", orig)


def _half_dropped() -> Callable[[], None]:
    batcher = importlib.import_module("repro.serve.batcher")

    orig = batcher.ticket_records

    def half(result, request):
        rows = orig(result, request)
        return rows[:len(rows) // 2]

    batcher.ticket_records = half
    return lambda: setattr(batcher, "ticket_records", orig)


def _route_altered() -> Callable[[], None]:
    route = importlib.import_module("repro.fabric.route")

    orig = route.route_nets

    def altered(*a, **k):
        res = orig(*a, **k)
        for net in res.nets:
            if net.edges:
                net.edges.pop()
                break
        return res

    route.route_nets = altered
    return lambda: setattr(route, "route_nets", orig)


def _sim_altered() -> Callable[[], None]:
    sim = importlib.import_module("repro.sim")

    orig = sim.simulate_batch

    def altered(progs, inputs_list, *a, **k):
        res = orig(progs, inputs_list, *a, **k)
        out = np.array(res[0].outputs, copy=True)
        out.flat[0] += 1.0
        res[0].outputs = out
        return res

    sim.simulate_batch = altered
    return lambda: setattr(sim, "simulate_batch", orig)


def _record_altered() -> Callable[[], None]:
    batcher = importlib.import_module("repro.serve.batcher")

    orig = batcher.ticket_records

    def altered(result, request):
        rows = orig(result, request)
        return [dataclasses.replace(r, sim_ii=r.sim_ii + 1) for r in rows]

    batcher.ticket_records = altered
    return lambda: setattr(batcher, "ticket_records", orig)


PLANTS: Dict[str, Callable[[], Callable[[], None]]] = {
    "anneal_unchanged": _anneal_unchanged,
    "half_dropped": _half_dropped,
    "route_altered": _route_altered,
    "sim_altered": _sim_altered,
    "record_altered": _record_altered,
}


def plant(name: str) -> Callable[[], None]:
    return PLANTS[name]()
