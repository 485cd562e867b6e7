"""Deterministic-seed tests for the fabric place-and-route subsystem."""

import numpy as np
import pytest

from repro.apps import image_graphs
from repro.core import baseline_datapath, map_application
from repro.core.dse import app_ops
from repro.fabric import (FabricSpec, extract_netlist, place,
                          place_and_route, route_nets, synthetic_netlist)
from repro.fabric.place import anneal_jax, anneal_python, lower, \
    net_incidence
from repro.kernels.pnr_cost import (HPWL_BLOCK_NETS, hpwl, hpwl_batched,
                                    hpwl_delta, hpwl_delta_pallas,
                                    hpwl_pallas, hpwl_reference, net_hpwl)

SPEC = FabricSpec(rows=8, cols=8)


@pytest.fixture(scope="module")
def harris():
    app = image_graphs()["harris"]
    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, "harris")
    netlist = extract_netlist(mapping, app, SPEC)
    return dp, mapping, app, netlist


# ---------------------------------------------------------------------------
# netlist
# ---------------------------------------------------------------------------
def test_netlist_const_folding_and_shape(harris):
    dp, mapping, app, nl = harris
    assert len(nl.pe_cells) == mapping.n_pes
    # consts are folded into PE constant registers: no cell carries one and
    # no net is driven by one
    const_nodes = {n for n, op in app.nodes.items() if op == "const"}
    for c in nl.io_cells:
        assert not (set(c.signals) & const_nodes)
    for n in nl.nets:
        assert n.signal not in const_nodes
        assert n.driver in nl.cells
        assert all(s in nl.cells for s in n.sinks)
        assert n.driver not in n.sinks
    # every net carries at least driver + one sink
    assert all(n.degree >= 2 for n in nl.nets)


def test_io_grouping_respects_capacity(harris):
    _, _, _, nl = harris
    for c in nl.io_cells:
        assert 1 <= len(c.signals) <= SPEC.io_capacity


# ---------------------------------------------------------------------------
# placement legality
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["python", "jax"])
def test_placement_legal(harris, backend):
    _, _, _, nl = harris
    pl = place(nl, SPEC, backend=backend, chains=4, sweeps=8, seed=1)
    coords = pl.coords
    # one cell per tile
    assert len(set(coords.values())) == len(coords)
    for cell in nl.pe_cells:
        assert SPEC.is_pe(coords[cell.name]), (cell.name, coords[cell.name])
    for cell in nl.io_cells:
        assert SPEC.is_io(coords[cell.name]), (cell.name, coords[cell.name])


def test_placement_deterministic(harris):
    _, _, _, nl = harris
    a = place(nl, SPEC, backend="jax", chains=4, sweeps=8, seed=3)
    b = place(nl, SPEC, backend="jax", chains=4, sweeps=8, seed=3)
    assert a.coords == b.coords and a.cost == b.cost


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
def test_routing_connects_endpoints_within_capacity(harris):
    _, _, _, nl = harris
    pl = place(nl, SPEC, backend="jax", chains=8, sweeps=16, seed=0)
    rr = route_nets(nl, pl, SPEC)
    assert rr.success and rr.overflow == 0
    caps = SPEC.routing_edges()
    for e, u in rr.edge_usage.items():
        assert u <= caps[e], (e, u, caps[e])
    by_name = {n.name: n for n in rr.nets}
    for net in nl.nets:
        routed = by_name[net.name]
        # the routed tree must connect the placed driver to every sink
        reach = {pl.coords[net.driver]}
        frontier = True
        while frontier:
            frontier = False
            for (a, b) in routed.edges:
                if a in reach and b not in reach:
                    reach.add(b)
                    frontier = True
        for s in net.sinks:
            assert pl.coords[s] in reach, (net.name, s)
        assert set(routed.sink_hops) == {pl.coords[s] for s in net.sinks}
        assert all(h >= 1 for h in routed.sink_hops.values())


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_fabric_cost_monotone_in_wirelength(harris):
    from repro.fabric.cost import evaluate_fabric

    dp, mapping, app, nl = harris
    good = place(nl, SPEC, backend="jax", chains=8, sweeps=16, seed=0)
    bad = place(nl, SPEC, backend="python", chains=1, sweeps=1, seed=9,
                t0=50.0, t1=49.0)   # hot chain = near-random placement
    rg = route_nets(nl, good, SPEC)
    rb = route_nets(nl, bad, SPEC)
    assert rg.wirelength < rb.wirelength
    cg = evaluate_fabric(dp, mapping, nl, good, rg, SPEC)
    cb = evaluate_fabric(dp, mapping, nl, bad, rb, SPEC)
    # same netlist: PE and IO energy identical; routing energy scales
    # exactly with hops, so total energy is monotone in wirelength
    assert cg.pe_energy_pj == cb.pe_energy_pj
    assert cg.io_energy_pj == cb.io_energy_pj
    assert cb.route_energy_pj - cg.route_energy_pj == pytest.approx(
        SPEC.hop_energy_pj * (rb.wirelength - rg.wirelength))
    assert cg.total_energy_pj < cb.total_energy_pj
    assert cg.energy_per_op_pj < cb.energy_per_op_pj


# ---------------------------------------------------------------------------
# HPWL kernels
# ---------------------------------------------------------------------------
def test_hpwl_jax_matches_python_reference(harris):
    _, _, _, nl = harris
    problem = lower(nl, SPEC)
    rng = np.random.default_rng(7)
    for _ in range(5):
        slot_of = np.concatenate([
            rng.permutation(problem.n_pe_slots),
            problem.n_pe_slots + rng.permutation(problem.n_io_slots)])
        pos = problem.slot_xy[slot_of]
        want = hpwl_reference(pos, problem.net_pins, problem.net_mask)
        got = float(hpwl(pos, problem.net_pins, problem.net_mask))
        assert got == pytest.approx(want)
        got_pl = float(hpwl_pallas(pos, problem.net_pins, problem.net_mask,
                                   interpret=True))
        assert got_pl == pytest.approx(want)


def test_hpwl_pallas_tiled_equals_hpwl():
    # a 32x32 synthetic netlist has more nets than one kernel block, so
    # the grid runs several steps; integer coordinates sum exactly
    spec = FabricSpec(rows=32, cols=32)
    problem = lower(synthetic_netlist(spec, seed=3), spec)
    assert problem.net_pins.shape[0] > HPWL_BLOCK_NETS
    rng = np.random.default_rng(11)
    slot_of = np.concatenate([
        rng.permutation(problem.n_pe_slots),
        problem.n_pe_slots + rng.permutation(problem.n_io_slots)])
    pos = problem.slot_xy[slot_of]
    want = hpwl(pos, problem.net_pins, problem.net_mask)
    got = hpwl_pallas(pos, problem.net_pins, problem.net_mask,
                      interpret=True)
    assert float(got) == float(want)


def test_hpwl_batched_matches_per_chain(harris):
    _, _, _, nl = harris
    problem = lower(nl, SPEC)
    rng = np.random.default_rng(3)
    pos = np.stack([problem.slot_xy[np.concatenate([
        rng.permutation(problem.n_pe_slots),
        problem.n_pe_slots + rng.permutation(problem.n_io_slots)])]
        for _ in range(6)])
    batched = np.asarray(hpwl_batched(pos, problem.net_pins,
                                      problem.net_mask))
    for c in range(pos.shape[0]):
        assert batched[c] == pytest.approx(
            hpwl_reference(pos[c], problem.net_pins, problem.net_mask))


def test_jax_annealer_improves_over_initial(harris):
    import random

    from repro.fabric.place import _init_slots

    _, _, _, nl = harris
    problem = lower(nl, SPEC)
    slots, costs = anneal_jax(problem, chains=4, seed=0, sweeps=8)
    # reconstruct the chains' initial states (same seed stream as anneal_jax)
    rng = random.Random(0)
    init_costs = []
    for _ in range(4):
        pos0 = problem.slot_xy[_init_slots(problem, rng)]
        init_costs.append(hpwl_reference(pos0, problem.net_pins,
                                         problem.net_mask))
    for c in range(slots.shape[0]):
        # results are consistent: reported cost == HPWL of returned state
        pos = problem.slot_xy[slots[c]]
        assert float(costs[c]) == pytest.approx(
            hpwl_reference(pos, problem.net_pins, problem.net_mask))
        # best-so-far tracking can never end worse than the initial state
        assert float(costs[c]) <= init_costs[c]
    # and annealing actually improves at least the best chain
    assert float(min(costs)) < min(init_costs)
    py_slot, py_cost = anneal_python(problem, seed=0, sweeps=8)
    # both engines land in the same quality ballpark on this small problem
    assert min(costs) < 2.0 * py_cost + 1.0


# ---------------------------------------------------------------------------
# delta (incremental) move scoring
# ---------------------------------------------------------------------------
def test_net_incidence_table(harris):
    _, _, _, nl = harris
    p = lower(nl, SPEC)
    n_nets = p.net_pins.shape[0]
    table = p.ent_nets
    assert table.shape[0] == p.n_entities
    for e in range(p.n_entities):
        want = sorted(i for i in range(n_nets)
                      if e in p.net_pins[i][p.net_mask[i]])
        got = sorted(int(i) for i in table[e] if i < n_nets)
        assert got == want, e
    # padding entries are exactly N so out-of-range gathers drop them
    assert table.min() >= 0 and table.max() <= n_nets


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_hpwl_delta_matches_full_recompute(harris, kernel):
    import jax.numpy as jnp

    _, _, _, nl = harris
    p = lower(nl, SPEC)
    n_nets = p.net_pins.shape[0]
    rng = np.random.default_rng(11)
    slot_of = np.concatenate([
        rng.permutation(p.n_pe_slots),
        p.n_pe_slots + rng.permutation(p.n_io_slots)]).astype(np.int32)
    pnc = np.asarray(net_hpwl(p.slot_xy[slot_of], p.net_pins, p.net_mask))
    k = p.ent_nets.shape[1]
    for _ in range(10):
        a, b = rng.integers(0, p.n_entities, 2)
        cand = slot_of.copy()
        cand[a], cand[b] = cand[b], cand[a]
        touched = np.full(2 * k, n_nets, np.int32)
        nets = sorted({int(i) for i in np.concatenate(
            [p.ent_nets[a], p.ent_nets[b]]) if i < n_nets})
        touched[:len(nets)] = nets
        if kernel == "jnp":
            new_vals, delta = hpwl_delta(
                jnp.asarray(p.slot_xy), jnp.asarray(cand),
                jnp.asarray(p.net_pins), jnp.asarray(p.net_mask),
                jnp.asarray(pnc), jnp.asarray(touched))
        else:
            new_vals, delta = hpwl_delta_pallas(
                jnp.asarray(p.slot_xy), jnp.asarray(slot_of),
                jnp.asarray(p.net_pins), jnp.asarray(p.net_mask),
                jnp.asarray(pnc), jnp.asarray(touched),
                jnp.int32(a), jnp.int32(b), interpret=True)
        want = hpwl_reference(p.slot_xy[cand], p.net_pins, p.net_mask)
        assert pnc.sum() + float(delta) == pytest.approx(want)
        # returned per-net values are the candidate costs of the touched nets
        cand_pnc = np.asarray(net_hpwl(p.slot_xy[cand], p.net_pins,
                                       p.net_mask))
        for t, i in enumerate(nets):
            assert float(new_vals[t]) == pytest.approx(cand_pnc[i])


def test_delta_full_bit_identical_16x16():
    """Deterministic regression: at 16x16 every (score_mode, hpwl_backend)
    combination accepts the same move sequence and returns bit-identical
    placements and costs."""
    spec = FabricSpec(rows=16, cols=16)
    p = lower(synthetic_netlist(spec, seed=2), spec)
    runs = {}
    for mode in ("delta", "full"):
        for hb in ("jnp", "pallas"):
            runs[(mode, hb)] = anneal_jax(p, chains=2, seed=7, sweeps=2,
                                          hpwl_backend=hb, score_mode=mode)
    ref_slots, ref_costs = runs[("full", "jnp")]
    for key, (slots, costs) in runs.items():
        assert np.array_equal(slots, ref_slots), key
        assert np.array_equal(costs, ref_costs), key
    # and the reported costs are real HPWLs of the returned states
    for c in range(ref_slots.shape[0]):
        assert float(ref_costs[c]) == pytest.approx(hpwl_reference(
            p.slot_xy[ref_slots[c]], p.net_pins, p.net_mask))


def test_place_rejects_unknown_score_mode(harris):
    _, _, _, nl = harris
    with pytest.raises(ValueError, match="score_mode"):
        place(nl, SPEC, score_mode="incremental")


def test_synthetic_netlist_is_deterministic_and_legal():
    spec = FabricSpec(rows=8, cols=8)
    a = synthetic_netlist(spec, seed=5)
    b = synthetic_netlist(spec, seed=5)
    assert [(n.name, n.driver, n.sinks) for n in a.nets] == \
           [(n.name, n.driver, n.sinks) for n in b.nets]
    assert len(a.pe_cells) <= spec.n_pe_tiles
    assert len(a.io_cells) <= spec.n_io_sites
    for n in a.nets:
        assert n.driver not in n.sinks and n.degree >= 2
        assert n.driver in a.cells
        assert all(s in a.cells for s in n.sinks)


# ---------------------------------------------------------------------------
# end to end + sizing
# ---------------------------------------------------------------------------
def test_spec_fit_grows_to_demand():
    s = FabricSpec(rows=2, cols=2)
    big = s.fit(30, 10)
    assert big.n_pe_tiles >= 30 and big.n_io_sites >= 10
    assert big.channel_width == s.channel_width
    assert s.fit(4, 8) is s


def test_place_and_route_end_to_end_auto_size(harris):
    dp, mapping, app, _ = harris
    pnr = place_and_route(dp, mapping, app, FabricSpec(rows=2, cols=2),
                          backend="python", chains=1, sweeps=8, seed=0)
    assert pnr.spec.n_pe_tiles >= mapping.n_pes
    assert pnr.routes.overflow == 0
    assert pnr.cost.energy_per_op_pj > 0
    assert 0 < pnr.cost.utilization <= 1.0
    assert pnr.cost.fmax_ghz > 0


def test_dse_fabric_integration():
    from repro.core.dse import PEVariant, evaluate_variants

    app = image_graphs()["gaussian"]
    dp = baseline_datapath(app_ops(app))
    v = PEVariant("PE1", dp)
    evaluate_variants([v], {"gaussian": app}, fabric=FabricSpec(8, 8),
                      fabric_backend="python", fabric_chains=1,
                      fabric_sweeps=8)
    c = v.costs["gaussian"]
    f = v.fabric_costs["gaussian"]
    assert c.fabric_energy_per_op_pj == pytest.approx(f.energy_per_op_pj)
    assert c.fabric_area_um2 == pytest.approx(f.fabric_area_um2)
    assert c.fabric_wirelength == f.wirelength_hops
    # array view adds interconnect: array e/op dominates PE-core e/op
    assert f.energy_per_op_pj > c.energy_per_op_pj
