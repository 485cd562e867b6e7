"""Only legal routes are served: the grouped placer routes its chains in
cost order until one fits the channels' track counts, and a pair that no
chain routes fails as ``Unroutable`` instead of becoming a record.

The configurations are the chip benchmark's own (``ml16`` and
``image_ip``), with simulation off, mined once per module."""

import dataclasses
import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.apps import image_graphs, ml_graphs
from repro.core import MiningConfig
from repro.core.dse import app_ops
from repro.core.mapper import map_application
from repro.core.merge import baseline_datapath
from repro.errors import Unroutable
from repro.explore import ExploreConfig, Explorer
from repro.fabric import (FabricOptions, FabricSpec, PnRResult, Placement,
                          evaluate_fabric, extract_netlist, lower,
                          place_and_route, route_nets)
from repro.graphir import trace_scalar

pipeline = importlib.import_module("repro.explore.pipeline")
fabric_place = importlib.import_module("repro.fabric.place")

CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks/chip/configs"

#: fabric seed at which camera's cheapest chain (PE_IP on 11x11 under the
#: image_ip configuration) ends the router's 8 iterations one track over
WITNESS_SEED = 50


def _config(name: str, seed: int) -> ExploreConfig:
    d = json.loads((CONFIGS / f"{name}.json").read_text())["explore"]
    cfg = ExploreConfig.from_dict(d)
    return cfg.replace(fabric=dataclasses.replace(cfg.fabric, seed=seed,
                                                  simulate=False))


@pytest.fixture(scope="module")
def image_ip():
    ex = Explorer(image_graphs(), _config("image_ip", 0))
    ex.map()
    return ex


@pytest.fixture(scope="module")
def ml16():
    ex = Explorer(ml_graphs(), _config("ml16", 0))
    ex.map()
    return ex


def _at_seed(ex: Explorer, seed: int) -> Explorer:
    return ex.with_config(fabric=dataclasses.replace(ex.config.fabric,
                                                     seed=seed))


def _capture(monkeypatch):
    """Every chain's slots and costs the annealer returns, by nonce, and
    the pairs the grouped placer is given."""
    annealed, items = {}, []
    anneal, grouped = fabric_place.anneal_jax_batch, pipeline.pnr_grouped

    def anneal_kept(problems, *a, nonces, **k):
        out = anneal(problems, *a, nonces=nonces, **k)
        annealed.update(zip(nonces, out))
        return out

    def grouped_kept(its, *a, **k):
        items.extend(its)
        return grouped(its, *a, **k)

    monkeypatch.setattr(fabric_place, "anneal_jax_batch", anneal_kept)
    monkeypatch.setattr(pipeline, "pnr_grouped", grouped_kept)
    return annealed, items


def _argmin_direct(annealed: dict):
    """The rule before legality: each pair's cheapest chain (by argmin),
    routed and served as it stands, from the anneal already run."""

    def pnr(items, options, stats=None, isolate=False):
        out = []
        for pe_name, dp, mapping, app, nonce in items:
            netlist = extract_netlist(mapping, app, options.spec)
            spec = options.spec.fit(len(netlist.pe_cells),
                                    len(netlist.io_cells))
            prob = lower(netlist, spec)
            slots, costs = annealed[nonce]
            best = int(np.argmin(costs))
            coords = {}
            for idx, name in enumerate(prob.cell_names):
                x, y = prob.slot_xy[slots[best][prob.entity_of(idx)]]
                coords[name] = (int(x), int(y))
            placement = Placement(coords=coords, cost=float(costs[best]),
                                  backend="jax", chains=options.chains,
                                  sweeps=options.sweeps,
                                  chain_costs=[float(c) for c in costs])
            routes = route_nets(netlist, placement, spec)
            out.append(PnRResult(spec, netlist, placement, routes,
                                 evaluate_fabric(dp, mapping, netlist,
                                                 placement, routes, spec,
                                                 pe_name=pe_name)))
        return out

    return pnr


def _illegal(pnr) -> int:
    """Tracks over capacity plus unreached sinks, counted from the
    coordinates alone: PE tiles are (0..cols-1, 0..rows-1), I/O sites one
    step outside; PE-PE channels carry ``channel_width`` tracks a
    direction and an I/O site's one channel to its PE ``io_capacity``."""
    spec = pnr.spec

    def pe(t):
        return 0 <= t[0] < spec.cols and 0 <= t[1] < spec.rows

    def capacity(a, b):
        if abs(a[0] - b[0]) + abs(a[1] - b[1]) != 1:
            return 0
        if pe(a) and pe(b):
            return spec.channel_width
        return spec.io_capacity if pe(a) or pe(b) else 0

    coords = pnr.placement.coords
    routed = {r.name: r for r in pnr.routes.nets}
    usage = {}
    bad = 0
    for net in pnr.netlist.nets:
        edges = {(tuple(a), tuple(b)) for a, b in routed[net.name].edges}
        nxt = {}
        for a, b in edges:
            usage[(a, b)] = usage.get((a, b), 0) + 1
            nxt.setdefault(a, []).append(b)
        reached, todo = set(), [tuple(coords[net.driver])]
        while todo:
            t = todo.pop()
            if t not in reached:
                reached.add(t)
                todo += nxt.get(t, [])
        bad += sum(tuple(coords[s]) not in reached for s in net.sinks)
    return bad + sum(max(0, n - capacity(*e)) for e, n in usage.items())


def test_the_witness_falls_back_to_a_legal_chain(image_ip, monkeypatch):
    ex = _at_seed(image_ip, WITNESS_SEED)
    ex.forget("pnr")
    annealed, items = _capture(monkeypatch)
    before = ex.metrics.histogram("pnr.route.fell_back").to_dict()
    obs.disable_tracing()                     # a tracer of this test alone
    tracer = obs.enable_tracing()
    try:
        served = ex.pnr()
    finally:
        obs.disable_tracing()
    assert sorted(a for _, a in served) == sorted(image_graphs())
    camera = served[("PE_IP", "camera")]
    assert (camera.spec.rows, camera.spec.cols) == (11, 11)
    for p in served.values():
        assert _illegal(p) == 0 and p.routes.success

    # the cheapest chain's routing is over capacity: the fault
    direct = _argmin_direct(annealed)(items, ex.config.fabric)
    over = {it[2].app_name for it, p in zip(items, direct) if _illegal(p)}
    assert over == {"camera"}

    spans = {sp.attrs["app"]: sp.attrs for sp, _, _ in tracer.iter_spans()
             if sp.name == "pnr.pair"}
    rank = spans["camera"]["chain_rank"]
    assert rank > 0 and spans["camera"]["tries"] == rank + 1
    assert spans["camera"]["overflow"] == 0 and spans["camera"]["ripups"] > 0
    assert camera.placement.cost == sorted(
        camera.placement.chain_costs)[rank]
    assert all(spans[a]["chain_rank"] == 0 for a in spans if a != "camera")
    after = ex.metrics.histogram("pnr.route.fell_back").to_dict()
    assert after["count"] - before["count"] == 4
    assert after["sum"] - before["sum"] == 1


@pytest.mark.parametrize("name, seed, over", [
    ("image_ip", WITNESS_SEED, {("PE_IP", "camera")}),
    ("image_ip", 7, set()), ("ml16", 3, set())])
def test_records_are_byte_identical_where_the_cheapest_chain_routes(
        name, seed, over, request, monkeypatch):
    ex = _at_seed(request.getfixturevalue(name), seed)
    ex.forget("pnr")
    annealed, _ = _capture(monkeypatch)
    served = {(r.pe_name, r.app): json.dumps(r.to_dict())
              for r in ex.run().records()}
    served_pnr = dict(ex.pnr())
    ex.forget("pnr")
    monkeypatch.setattr(pipeline, "pnr_grouped", _argmin_direct(annealed))
    try:
        direct = {(r.pe_name, r.app): json.dumps(r.to_dict())
                  for r in ex.run().records()}
        direct_pnr = dict(ex.pnr())
    finally:
        ex.forget("pnr")
    assert set(served) == set(direct) == set(direct_pnr)
    assert {pair for pair, p in direct_pnr.items()
            if not p.routes.success} == over
    for pair in set(served) - over:
        assert served[pair] == direct[pair], pair
        assert served_pnr[pair].placement == direct_pnr[pair].placement
        assert served_pnr[pair].routes == direct_pnr[pair].routes


def _fan_out():
    def fan(a, b, c, d, e):
        return (a * b, a * c, a * d, a * e, b * c, b * d, b * e, c * d,
                c * e, d * e)
    return trace_scalar(fan, list("abcde"))


def _fan_config(**changes) -> ExploreConfig:
    spec = FabricSpec(2, 2, channel_width=1)
    return ExploreConfig(mode="per_app",
                         mining=MiningConfig(min_support=2,
                                             max_pattern_nodes=3),
                         max_merge=1,
                         fabric=FabricOptions(spec=spec, chains=2, sweeps=4),
                         **changes)


def test_an_unroutable_pair_is_a_stage_failure_not_a_routing():
    ex = Explorer({"fan": _fan_out()}, _fan_config())
    res = ex.run()
    # degraded as any failed pair: mapping-level columns, no array ones
    [rec] = res.records()
    assert (rec.fabric_area_um2, rec.fabric_wirelength) == (0.0, 0)
    [failure] = res.failures
    assert (failure.stage, failure.error_type) == ("pnr", "Unroutable")
    assert not failure.retried
    assert ex.metrics.counter("pnr.route.unroutable") == 1
    assert ex.metrics.histogram("pnr.route.fell_back").count == 0

    with pytest.raises(Unroutable) as err:
        Explorer({"fan": _fan_out()}, _fan_config(on_error="raise")).pnr()
    assert err.value.overflow > 0


def test_the_serial_place_and_route_refuses_an_illegal_routing():
    app = _fan_out()
    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, "fan")
    with pytest.raises(Unroutable) as err:
        place_and_route(dp, mapping, app, FabricSpec(2, 2, channel_width=1),
                        chains=2, sweeps=4)
    assert err.value.overflow > 0
    legal = place_and_route(dp, mapping, app, FabricSpec(2, 2),
                            chains=2, sweeps=4)
    assert legal.routes.success and _illegal(legal) == 0
