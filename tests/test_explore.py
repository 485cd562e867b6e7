"""The staged exploration pipeline (repro.explore): config/record schema,
stage memoization, batch-first pnr, and bit-identical legacy shims."""

import dataclasses
import json

import numpy as np
import pytest

from repro.apps import image
from repro.core import MiningConfig, mine_and_rank, specialize_per_app
from repro.core.dse import DSEResult, PEVariant, build_variants, \
    evaluate_variants
from repro.core.costmodel import AppCost
from repro.explore import (ExploreConfig, ExploreRecord, Explorer,
                           RECORD_SCHEMA, from_jsonl, to_jsonl)
from repro.fabric import FabricOptions, FabricSpec
from repro.graphir import trace_scalar

#: fast but budget-unbound mining: deterministic run to run
FAST = MiningConfig(min_support=4, max_pattern_nodes=4, time_budget_s=120,
                    max_patterns_per_level=30)


def conv_app():
    def conv4(i0, i1, i2, i3, w0, w1, w2, w3, c):
        return (((i0 * w0) + (i1 * w1)) + (i2 * w2)) + (i3 * w3) + c
    return trace_scalar(conv4, ["i0", "i1", "i2", "i3",
                                "w0", "w1", "w2", "w3", "c"])


@pytest.fixture(scope="module")
def camera():
    return image.build_graph("camera")


# ---------------------------------------------------------------------------
# schema / serialization
# ---------------------------------------------------------------------------
#: frozen golden schema — changing ExploreRecord requires bumping
#: RECORD_SCHEMA and updating this list in the same commit
RECORD_FIELDS = [
    "schema", "mode", "config_key", "n_merged", "sim_bucket",
    "app", "pe_name", "n_pes", "total_ops", "pe_area_um2", "total_area_um2",
    "energy_pj", "energy_per_op_pj", "fmax_ghz", "ops_per_pe", "unmapped",
    "cgra_area_um2", "cgra_energy_pj", "cgra_energy_per_op_pj",
    "fabric_area_um2", "fabric_energy_per_op_pj", "fabric_fmax_ghz",
    "fabric_wirelength", "fabric_utilization",
    "sim_ii", "sim_min_ii", "sim_latency_cycles", "sim_active_frac",
    "sim_throughput_gops", "sim_energy_per_op_pj", "sim_verified",
]


def test_record_golden_schema_and_jsonl_round_trip(tmp_path):
    assert [f.name for f in dataclasses.fields(ExploreRecord)] \
        == RECORD_FIELDS
    # the AppCost column subset must track costmodel.AppCost exactly
    appcost_fields = [f.name for f in dataclasses.fields(AppCost)]
    assert RECORD_FIELDS[5:] == appcost_fields

    cost = AppCost(app="a", pe_name="PE1", n_pes=3, total_ops=7,
                   pe_area_um2=1.5, total_area_um2=4.5, energy_pj=2.0,
                   energy_per_op_pj=0.3, fmax_ghz=1.1, ops_per_pe=2.3,
                   unmapped=0)
    rec = ExploreRecord.from_cost(cost, mode="per_app", config_key="k",
                                  n_merged=2)
    assert rec.schema == RECORD_SCHEMA
    path = str(tmp_path / "r.jsonl")
    assert to_jsonl([rec], path) == 1
    back = from_jsonl(path)
    assert len(back) == 1 and back[0] == rec

    # unknown schema versions fail loudly
    bad = rec.to_dict() | {"schema": RECORD_SCHEMA + 1}
    with open(path, "w") as f:
        f.write(json.dumps(bad) + "\n")
    with pytest.raises(ValueError, match="schema"):
        from_jsonl(path)


def test_explore_config_json_round_trip():
    cfg = ExploreConfig(
        mode="domain", mining=FAST, max_merge=2, rank_mode="utility",
        per_app_subgraphs=3, domain_name="PE_X",
        fabric=FabricOptions(spec=FabricSpec(rows=6, cols=5), chains=3,
                             sweeps=9, seed=7, simulate=True),
        pnr_batch="serial", sim_batch="serial")
    blob = json.dumps(cfg.to_dict())
    assert ExploreConfig.from_dict(json.loads(blob)) == cfg
    # no-fabric config round-trips too
    cfg2 = ExploreConfig(mining=FAST)
    assert ExploreConfig.from_dict(cfg2.to_dict()) == cfg2
    with pytest.raises(ValueError, match="schema"):
        ExploreConfig.from_dict(cfg.to_dict() | {"schema": 99})
    with pytest.raises(ValueError, match="unknown"):
        ExploreConfig.from_dict(cfg2.to_dict() | {"bogus": 1})


def test_config_rejects_bad_values():
    with pytest.raises(ValueError, match="mode"):
        ExploreConfig(mode="nope")
    with pytest.raises(ValueError, match="pnr_batch"):
        ExploreConfig(pnr_batch="nope")
    with pytest.raises(ValueError, match="sim_batch"):
        ExploreConfig(sim_batch="nope")
    with pytest.raises(ValueError, match="rank_mode"):
        ExploreConfig(rank_mode="nope")


def test_records_same_bytes_under_either_failure_policy():
    # the service runs every request isolated; a clean run's records must
    # equal a fail-fast solo run's byte for byte (config_key drops on_error)
    from repro.explore.__main__ import _smoke_case
    from repro.explore.pipeline import config_key

    apps, cfg = _smoke_case()
    lines = {}
    for policy in ("isolate", "raise"):
        res = Explorer(apps, cfg.replace(on_error=policy)).run()
        assert res.clean
        lines[policy] = [json.dumps(r.to_dict()) for r in res.records()]
    assert lines["isolate"] and lines["isolate"] == lines["raise"]
    assert config_key(cfg) != config_key(cfg.replace(max_merge=1))


# ---------------------------------------------------------------------------
# stage memoization
# ---------------------------------------------------------------------------
def test_stage_memoization_zero_remines():
    apps = {"conv": conv_app()}
    fabric = FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                           sweeps=4)
    cfg = ExploreConfig(mode="per_app",
                        mining=MiningConfig(min_support=2,
                                            max_pattern_nodes=5),
                        max_merge=2, fabric=fabric)
    ex = Explorer(apps, cfg)
    res1 = ex.run()
    assert ex.stats["mine"] == 1
    upstream = {k: ex.stats[k] for k in ("mine", "rank", "merge", "map")}
    pnr_runs = ex.stats["pnr"]

    # identical config: the whole pipeline is a cache hit
    res_again = ex.run()
    assert {k: ex.stats[k] for k in upstream} == upstream
    assert ex.stats["pnr"] == pnr_runs

    # downstream-only change (annealing budget): zero re-mines/merges/maps,
    # but the pnr stage re-runs
    ex2 = ex.with_config(fabric=dataclasses.replace(fabric, sweeps=6))
    res2 = ex2.run()
    assert {k: ex2.stats[k] for k in upstream} == upstream
    assert ex2.stats["pnr"] > pnr_runs

    # flipping simulate on reuses mine AND pnr artifacts
    pnr_runs2 = ex2.stats["pnr"]
    ex3 = ex2.with_config(
        fabric=dataclasses.replace(fabric, sweeps=6, simulate=True))
    res3 = ex3.run()
    assert {k: ex3.stats[k] for k in upstream} == upstream
    assert ex3.stats["pnr"] == pnr_runs2
    rec3 = res3.records()
    assert all(r.sim_ii > 0 and r.sim_verified == 1 for r in rec3)
    # the upstream columns are identical across the sim flip
    for a, b in zip(res2.records(), rec3):
        assert (a.app, a.pe_name, a.energy_per_op_pj,
                a.fabric_wirelength) \
            == (b.app, b.pe_name, b.energy_per_op_pj, b.fabric_wirelength)


# ---------------------------------------------------------------------------
# batch-first pnr
# ---------------------------------------------------------------------------
def test_grouped_pnr_matches_serial_structure_and_is_deterministic():
    apps = {"conv": conv_app()}
    fabric = FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                           sweeps=4)
    cfg = ExploreConfig(mode="per_app",
                        mining=MiningConfig(min_support=2,
                                            max_pattern_nodes=5),
                        max_merge=2, fabric=fabric, pnr_batch="grouped")
    ex = Explorer(apps, cfg)
    grouped = ex.pnr()
    assert ex.stats["pnr_dispatch"] >= 1
    # the CI-claimed dispatch count is a metrics-registry read, not a
    # separate hand-ticked counter: stats is a live view over ex.metrics,
    # and the registry agrees with the distinct batch signatures placed
    assert ex.stats.registry is ex.metrics
    assert ex.metrics.counter("pnr_dispatch") == ex.stats["pnr_dispatch"]
    from repro.fabric import batch_signature, lower
    sigs = {batch_signature(lower(p.netlist, p.spec), cfg.fabric.sweeps)
            for p in grouped.values()}
    assert ex.metrics.counter("pnr_dispatch") == len(sigs)
    assert ex.metrics.counter("memo.miss.pnr") == len(grouped)
    serial = ex.with_config(pnr_batch="serial").pnr()
    assert set(grouped) == set(serial)
    for pair in grouped:
        g, s = grouped[pair], serial[pair]
        # same netlist and fitted grid; both legally routed
        assert (g.spec.rows, g.spec.cols) == (s.spec.rows, s.spec.cols)
        assert len(g.netlist.nets) == len(s.netlist.nets)
        assert g.routes.success and s.routes.success
        assert g.cost.energy_per_op_pj > 0
        # every placement coordinate is a distinct legal tile
        coords = list(g.placement.coords.values())
        assert len(set(coords)) == len(coords)

    # grouped placement is deterministic (fresh store, same config)
    again = Explorer(apps, cfg).pnr()
    for pair in grouped:
        assert grouped[pair].placement.coords == again[pair].placement.coords
        assert grouped[pair].cost == again[pair].cost


def test_anneal_jax_batch_grouping_independent():
    from repro.fabric import (anneal_jax_batch, batch_signature, lower,
                              synthetic_netlist)
    spec = FabricSpec(rows=4, cols=4)
    p1 = lower(synthetic_netlist(spec, fill=0.8, seed=1), spec)
    p2 = lower(synthetic_netlist(spec, fill=0.8, seed=3), spec)
    assert batch_signature(p1, 8) == batch_signature(p2, 8)
    both = anneal_jax_batch([p1, p2], chains=2, seed=0, sweeps=8,
                            nonces=[11, 22])
    solo = anneal_jax_batch([p1], chains=2, seed=0, sweeps=8, nonces=[11])
    assert np.array_equal(both[0][0], solo[0][0])
    assert np.array_equal(both[0][1], solo[0][1])
    # reported cost is the true HPWL of the returned placement
    from repro.kernels.pnr_cost import hpwl_reference
    for p, (slots, costs) in zip([p1, p2], both):
        best = int(np.argmin(costs))
        assert hpwl_reference(p.slot_xy[slots[best]], p.net_pins,
                              p.net_mask) == pytest.approx(costs[best])
        for c in range(slots.shape[0]):
            assert sorted(slots[c]) == list(range(p.n_entities))


# ---------------------------------------------------------------------------
# batch-first schedule/simulate
# ---------------------------------------------------------------------------
def test_sim_stage_grouped_matches_serial():
    """The batched schedule/simulate stages are a pure throughput change:
    II, latency, verification flags, and every record column except the
    sim_bucket provenance must match the per-pair loop exactly."""
    apps = {"conv": conv_app()}
    fabric = FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                           sweeps=4, simulate=True)
    cfg = ExploreConfig(mode="per_app",
                        mining=MiningConfig(min_support=2,
                                            max_pattern_nodes=5),
                        max_merge=2, fabric=fabric)
    grouped_ex = Explorer(apps, cfg)
    grouped = grouped_ex.run()
    assert grouped_ex.stats["sim_dispatch"] >= 1
    assert grouped_ex.stats["sched_group"] >= 1
    # dispatch claims are registry reads: the sim stage's own counter and
    # the cycle-level bucket provenance must agree, and the run's result
    # carries the registry snapshot
    assert grouped_ex.stats.registry is grouped_ex.metrics
    assert grouped_ex.metrics.counter("sim.dispatch") \
        == grouped_ex.metrics.counter("sim_dispatch")
    assert grouped_ex.metrics.counter("sched_rounds") >= 1
    snap = grouped.metrics["counters"]
    assert snap["sim_dispatch"] == grouped_ex.stats["sim_dispatch"]
    assert snap["pnr_dispatch"] == grouped_ex.stats["pnr_dispatch"]
    serial = Explorer(apps, cfg.replace(sim_batch="serial")).run()

    g_rows = grouped.records()
    s_rows = serial.records()
    assert len(g_rows) == len(s_rows) > 0
    for g, s in zip(g_rows, s_rows):
        assert g.sim_ii == s.sim_ii > 0
        assert g.sim_verified == s.sim_verified == 1
        assert g.sim_bucket != "serial" and s.sim_bucket == "serial"
        gd, sd = g.to_dict(), s.to_dict()
        for d in (gd, sd):
            d.pop("sim_bucket")
            d.pop("config_key")        # differs: sim_batch is in the config
        assert gd == sd

    # flipping sim_batch re-uses every stage upstream of schedule
    upstream = {k: grouped_ex.stats[k]
                for k in ("mine", "rank", "merge", "map", "pnr")}
    ex2 = grouped_ex.with_config(sim_batch="serial")
    ex2.run()
    assert {k: ex2.stats[k] for k in upstream} == upstream


# ---------------------------------------------------------------------------
# legacy shims
# ---------------------------------------------------------------------------
def test_shim_equivalence_fig8_camera(camera):
    """old specialize_per_app == new Explorer, bit-identical (fixed seed)."""
    # the pre-redesign composition, inlined: mine+rank -> variants -> eval
    ranked = mine_and_rank(camera, FAST)
    variants = build_variants("camera", camera, ranked, max_merge=2)
    evaluate_variants(variants, {"camera": camera})
    old = [dataclasses.asdict(v.costs["camera"]) for v in variants]
    old_names = [(v.name, tuple(v.merged_subgraphs)) for v in variants]

    res = specialize_per_app({"camera": camera}, FAST, max_merge=2)["camera"]
    new = [dataclasses.asdict(v.costs["camera"]) for v in res.variants]
    new_names = [(v.name, tuple(v.merged_subgraphs)) for v in res.variants]
    assert old_names == new_names
    assert old == new
    assert [m.label for m in res.mined["camera"]] \
        == [m.label for m in ranked]


def test_shim_equivalence_with_fabric():
    apps = {"conv": conv_app()}
    mining = MiningConfig(min_support=2, max_pattern_nodes=5)
    opts = FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                         sweeps=4, seed=3)
    ranked = mine_and_rank(apps["conv"], mining)
    variants = build_variants("conv", apps["conv"], ranked, max_merge=1)
    evaluate_variants(variants, apps, fabric=opts)
    old = [dataclasses.asdict(v.costs["conv"]) for v in variants]

    res = specialize_per_app(apps, mining, max_merge=1, fabric=opts)["conv"]
    new = [dataclasses.asdict(v.costs["conv"]) for v in res.variants]
    assert old == new
    assert all(r["fabric_wirelength"] > 0 for r in new)


def test_legacy_fabric_kwargs_warn_and_match():
    apps = {"conv": conv_app()}
    mining = MiningConfig(min_support=2, max_pattern_nodes=5)
    spec = FabricSpec(rows=4, cols=4)
    with pytest.warns(DeprecationWarning, match="fabric_"):
        res_legacy = specialize_per_app(apps, mining, max_merge=1,
                                        fabric=spec, fabric_chains=2,
                                        fabric_sweeps=4, fabric_seed=3)
    res_new = specialize_per_app(
        apps, mining, max_merge=1,
        fabric=FabricOptions(spec=spec, chains=2, sweeps=4, seed=3))
    old = [dataclasses.asdict(v.costs["conv"])
           for v in res_legacy["conv"].variants]
    new = [dataclasses.asdict(v.costs["conv"])
           for v in res_new["conv"].variants]
    assert old == new


# ---------------------------------------------------------------------------
# best_variant: measured energy preferred over the static estimate
# ---------------------------------------------------------------------------
def _fake_cost(app, pe, static, sim=0.0, sim_ii=0):
    return AppCost(app=app, pe_name=pe, n_pes=1, total_ops=1,
                   pe_area_um2=1, total_area_um2=1, energy_pj=static,
                   energy_per_op_pj=static, fmax_ghz=1, ops_per_pe=1,
                   unmapped=0, sim_energy_per_op_pj=sim, sim_ii=sim_ii)


def test_best_variant_prefers_measured_sim_energy():
    from repro.core.pe import Datapath
    dp = Datapath()
    # statically PE_b looks best, but measured (skew-bound) energy says PE_a
    a = PEVariant("PE_a", dp)
    a.costs["app"] = _fake_cost("app", "PE_a", static=2.0, sim=3.0, sim_ii=4)
    b = PEVariant("PE_b", dp)
    b.costs["app"] = _fake_cost("app", "PE_b", static=1.0, sim=5.0, sim_ii=9)
    res = DSEResult({}, {}, [a, b])
    assert res.best_variant("app").name == "PE_a"

    # without simulation (sim_ii == 0) the static estimate still decides
    c = PEVariant("PE_c", dp)
    c.costs["app"] = _fake_cost("app", "PE_c", static=0.5)
    res2 = DSEResult({}, {}, [a, b, c])
    assert res2.best_variant("app").name == "PE_c"
