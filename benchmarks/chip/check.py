"""Decide ``correct``: what the window produced against the reference.

The harness keeps references to what the timed path itself produced
(:class:`Captures`): every placement and routing the annealer's dispatch
returned, every simulated output the batched stepper returned with the
input vectors it ran on, and every record the service sent back.  After
the window :func:`numbers` holds them to :mod:`reference`:

``requests_failed``       responses not ok, with StageFailure rows, or
                          whose (PE, app) pairs are not the ones the
                          configuration states (``served_pairs``)
``pairs_unchecked``       served records whose placement (or, when the
                          request simulates, whose simulated outputs) the
                          harness never saw
``placement_violations``  cells unplaced, on a wrong tile, or sharing one
``hpwl_gap``              largest |reported HPWL - exact HPWL|
``route_violations``      unreached sinks, non-channels, overused tracks
``wirelength_gap``        largest |record's fabric_wirelength - channels
                          the routes use|
``hpwl_ratio``            HPWL of all placements over that of random legal
                          placements of the same netlists: an annealer
                          that moves nothing reads about 1
``sim_max_abs_err``       largest |simulated output - reference output|
``record_mismatches``     served records whose array utilization, II,
                          latency or golden flag disagree with the
                          placement and the simulation the window ran

The reference computes in the datapath precision the configuration
states (``datapath``).  ``control=True`` puts the reference in the
program's place: the simulated outputs and the reported HPWL are the
reference's own in the next precision below (:data:`LOWER`), and the
routes (with the wirelength reported for them) are
:func:`reference.blind_routes`, which ignore the track count the
configuration states.  The limits in each configuration file reject it.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

import reference

Key = Tuple[int, str, str]            # (fabric seed, PE name, app name)

#: the control's precision: the one below each datapath precision
LOWER = {"float32": "bfloat16"}


@dataclass
class Captures:
    """References to what the timed path produced, keyed by pair."""

    pnr: Dict[Key, Any] = field(default_factory=dict)
    sim: Dict[Key, Tuple[List[str], np.ndarray, np.ndarray]] = field(
        default_factory=dict)
    schedule: Dict[Key, Tuple[int, int]] = field(default_factory=dict)
    _key_of: Dict[int, Tuple[Key, Any]] = field(default_factory=dict)

    def clear(self) -> None:
        self.pnr.clear()
        self.sim.clear()
        self.schedule.clear()
        self._key_of.clear()

    def add_pnr(self, seed: int, items, results) -> None:
        for (pe_name, _dp, mapping, _app, _nonce), res in zip(items,
                                                              results):
            key = (int(seed), pe_name, mapping.app_name)
            self.pnr[key] = res
            self._key_of[id(res)] = (key, res)

    def link_programs(self, items, progs) -> None:
        for (_dp, _mapping, _app, pnr), prog in zip(items, progs):
            hit = self._key_of.get(id(pnr))
            if hit is not None:
                self._key_of[id(prog)] = (hit[0], prog)

    def add_sim(self, progs, inputs_list, results) -> None:
        for prog, x, res in zip(progs, inputs_list, results):
            hit = self._key_of.get(id(prog))
            if hit is not None:
                self.sim[hit[0]] = (list(prog.input_names),
                                    np.asarray(x, np.float32),
                                    np.asarray(res.outputs, np.float32))
                self.schedule[hit[0]] = (int(prog.ii), int(prog.latency))


@dataclass
class Served:
    """One request of the window as the client saw it."""

    seed: int
    simulate: bool
    response: Dict[str, Any]
    latency_s: float
    sent: float                       # perf_counter at the send


def _pair_rng(run_seed: int, key: Key) -> np.random.Generator:
    tag = zlib.crc32(f"{key[0]}:{key[1]}:{key[2]}".encode())
    return np.random.default_rng([run_seed & 0xFFFFFFFF, run_seed >> 32,
                                  tag])


def _pnr_numbers(pnr, mesh: Mapping[str, int], run_seed: int, key: Key,
                 precision: Optional[str]) -> Dict[str, float]:
    """Placement and routing numbers of one pair; with a ``precision``,
    the control's: the reference's HPWL in it and blind routes."""
    spec, netlist, placement = pnr.spec, pnr.netlist, pnr.placement
    rows, cols = spec.rows, spec.cols
    coords = {n: (int(c[0]), int(c[1])) for n, c in placement.coords.items()}
    kinds = {name: cell.kind for name, cell in netlist.cells.items()}
    bad_place = reference.placement_violations(kinds, coords, rows, cols)
    nets = [[coords.get(n.driver, (0, 0))] + [coords.get(s, (0, 0))
                                             for s in n.sinks]
            for n in netlist.nets]
    exact = reference.hpwl(nets)
    reported = (float(placement.cost) if precision is None
                else reference.hpwl(nets, precision))
    routed = {r.name: r for r in pnr.routes.nets}
    route_in = []
    for pins, n in zip(nets, netlist.nets):
        if precision is None:
            r = routed.get(n.name)
            edges = r.edges if r else []
        else:
            edges = reference.blind_routes(pins[0], pins[1:], rows, cols)
        route_in.append((pins[0], pins[1:], edges))
    bad_route, wirelength = reference.route_violations(
        route_in, rows, cols, mesh["channel_width"], mesh["io_capacity"])
    index = {name: i for i, name in enumerate(kinds)}
    random = reference.random_hpwl(
        list(kinds.values()),
        [[index[n.driver]] + [index[s] for s in n.sinks]
         for n in netlist.nets],
        rows, cols, _pair_rng(run_seed, key))
    return {"placement_violations": bad_place,
            "hpwl_gap": abs(reported - exact),
            "route_violations": bad_route, "wirelength": wirelength,
            "reported_wirelength": (None if precision is None
                                    else wirelength),
            "hpwl": exact, "random_hpwl": random}


def _sim_error(graph: Mapping, names: List[str], inputs: np.ndarray,
               outputs: np.ndarray, datapath: str,
               control: Optional[str]) -> float:
    b, k, _ = inputs.shape
    feed = {name: inputs[:, :, j].reshape(-1) for j, name in
            enumerate(names)}
    want = reference.evaluate(graph, feed, datapath)
    if control is not None:
        got = reference.evaluate(graph, feed, control)
    else:
        got = [outputs[:, :, j].reshape(-1) for j in range(len(want))]
    err = 0.0
    for g, w in zip(got, want):
        d = np.abs(np.asarray(g, np.float64) - np.asarray(w, np.float64))
        if d.size and not np.all(np.isfinite(d)):
            return math.inf
        err = max(err, float(d.max(initial=0.0)))
    return err


def _record_mismatch(rec: Mapping, pnr, schedule: Optional[Tuple[int, int]],
                     simulate: bool, sim_ok: bool) -> bool:
    """Whether a served record disagrees with what the window produced:
    its array utilization with the placed netlist, and its II, latency
    and golden flag with the simulation that ran (or, without one, with
    the record of an unsimulated pair)."""
    pe_cells = sum(c.kind == "pe" for c in pnr.netlist.cells.values())
    if rec.get("fabric_utilization") != pe_cells / (pnr.spec.rows
                                                    * pnr.spec.cols):
        return True
    if not simulate:
        return rec.get("sim_ii") != 0 or rec.get("sim_verified") != -1
    ii, latency = schedule
    return (rec.get("sim_ii") != ii or ii < 1
            or rec.get("sim_latency_cycles") != latency
            or rec.get("sim_verified") != (1 if sim_ok else 0))


def _pair_keys(records: List[Mapping]) -> List[Tuple[str, str]]:
    return sorted((str(r.get("pe_name")), str(r.get("app")))
                  for r in records)


def numbers(served: List[Served], captures: Captures, suite: Mapping,
            config: Mapping, run_seed: int,
            control: bool = False) -> Dict[str, float]:
    """The numbers ``correct`` compares, for one window.  ``config`` is the
    configuration file: its ``mesh``, ``datapath`` and ``served_pairs``."""
    datapath = config["datapath"]
    lower = LOWER[datapath] if control else None
    mesh = config["mesh"]
    pairs = sorted((pe, app) for pe, app in config["served_pairs"])
    out = {"requests_failed": 0, "pairs_unchecked": 0,
           "placement_violations": 0, "hpwl_gap": 0.0,
           "route_violations": 0, "wirelength_gap": 0.0,
           "hpwl_ratio": 0.0, "record_mismatches": 0}
    simulated = any(s.simulate for s in served)
    if simulated:
        out["sim_max_abs_err"] = 0.0
    hpwl_sum = random_sum = 0.0
    for s in served:
        r = s.response
        records = r.get("records") or []
        if (not r.get("ok") or r.get("failures")
                or _pair_keys(records) != pairs):
            out["requests_failed"] += 1
        for rec in records:
            key = (s.seed, rec.get("pe_name"), rec.get("app"))
            pnr = captures.pnr.get(key)
            if pnr is None or isinstance(pnr, Exception):
                out["pairs_unchecked"] += 1
                continue
            got = _pnr_numbers(pnr, mesh, run_seed, key, lower)
            out["placement_violations"] += got["placement_violations"]
            out["route_violations"] += got["route_violations"]
            out["hpwl_gap"] = max(out["hpwl_gap"], got["hpwl_gap"])
            reported = got["reported_wirelength"]
            if reported is None:
                reported = float(rec.get("fabric_wirelength", -1))
            out["wirelength_gap"] = max(out["wirelength_gap"],
                                        abs(reported - got["wirelength"]))
            hpwl_sum += got["hpwl"]
            random_sum += got["random_hpwl"]
            if not s.simulate:
                out["record_mismatches"] += _record_mismatch(
                    rec, pnr, None, False, False)
                continue
            sim = captures.sim.get(key)
            graph = suite.get(rec.get("app"))
            if sim is None or graph is None:
                out["pairs_unchecked"] += 1
                continue
            err = _sim_error(graph, *sim, datapath=datapath, control=lower)
            out["sim_max_abs_err"] = max(out["sim_max_abs_err"], err)
            out["record_mismatches"] += _record_mismatch(
                rec, pnr, captures.schedule[key], True, err == 0.0)
    out["hpwl_ratio"] = hpwl_sum / random_sum if random_sum else math.inf
    return {k: float(v) for k, v in out.items()}


def verdict(values: Mapping[str, float],
            limits: Mapping[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """(every number within its limit, {name: {value, limit}})."""
    shown = {}
    ok = True
    for name, value in values.items():
        limit = limits[name]
        shown[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, shown
