"""Time-domain subsystem tests: modulo scheduler + cycle-accurate simulator.

Golden rule of this file: simulated outputs must equal
``graphir.interp`` BIT FOR BIT — the suite apps use only IEEE-exact ops,
so any tolerance would hide real mapping/scheduling bugs.
"""

import numpy as np
import pytest

from repro.apps import image_graphs, ml_graphs
from repro.core import baseline_datapath, map_application
from repro.core.dse import PEVariant, app_ops, evaluate_variants
from repro.fabric import FabricOptions, FabricSpec, place_and_route
from repro.sim import (build_sim, check_against_interp, min_ii,
                       modulo_schedule, random_inputs, simulate,
                       verify_mapping)
from repro.sim.schedule import L_LATCH, L_OUT, route_timing

SPEC = FabricSpec(rows=8, cols=8)
FAST = dict(place_backend="python", chains=1, sweeps=8)


def _flow(name, app):
    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, name)
    return dp, mapping


@pytest.fixture(scope="module")
def gaussian_sim():
    app = image_graphs()["gaussian"]
    dp, mapping = _flow("gaussian", app)
    prog, pnr = build_sim(dp, mapping, app, SPEC, **FAST)
    return app, dp, mapping, prog, pnr


# ---------------------------------------------------------------------------
# modulo schedule legality
# ---------------------------------------------------------------------------
def test_schedule_reports_and_respects_windows(gaussian_sim):
    app, dp, mapping, prog, pnr = gaussian_sim
    s = prog.schedule
    assert s.ii >= s.min_ii >= 1
    assert s.rec_mii == 1                      # app graphs are acyclic
    assert s.latency > 0 and s.attempts >= 1
    # every op scheduled exactly once, at a non-negative cycle
    kinds = {k for k, _ in s.start}
    assert kinds <= {"pe", "in"}
    assert all(t >= 0 for t in s.start.values())
    assert len([k for k in s.start if k[0] == "pe"]) == mapping.n_pes
    # hop slots: every routed hop holds data exactly depth+1 cycles after
    # its producer fires (the (cycle, II) slot assignment of the issue)
    routed = {n.name: n for n in pnr.routes.nets}
    cells = pnr.netlist.cells
    src_of = {}
    for net in pnr.netlist.nets:
        drv = cells[net.driver]
        src_of[net.name] = (("pe", drv.instance) if drv.kind == "pe"
                            else ("in", net.signal))
    assert s.hop_time
    for (net_name, tile), t in s.hop_time.items():
        nt = route_timing(routed[net_name])
        assert t == s.start[src_of[net_name]] + L_OUT + nt.depth[tile]


def test_min_ii_lower_bound_deterministic():
    """An I/O tile streaming k signals bounds II from below by k."""
    app = image_graphs()["gaussian"]          # 9 inputs
    dp, mapping = _flow("gaussian", app)
    for io_cap, want in [(4, 3), (2, 2), (1, 1)]:
        spec = FabricSpec(rows=8, cols=8, io_capacity=io_cap)
        pnr = place_and_route(dp, mapping, app, spec, backend="python",
                              chains=1, sweeps=8)
        rec, res = min_ii(pnr.netlist, pnr.routes, pnr.spec, pnr.placement)
        assert rec == 1
        assert res >= want                    # k signals share one io tile
        sched = modulo_schedule(pnr.netlist, pnr.placement, pnr.routes,
                                pnr.spec)
        assert sched.ii >= res                # achieved II >= resource bound
    # with io_capacity=4, gaussian's 9 inputs pack 4+4+1 -> ResMII == 4
    pnr = place_and_route(dp, mapping, app, FabricSpec(8, 8),
                          backend="python", chains=1, sweeps=8)
    _, res = min_ii(pnr.netlist, pnr.routes, pnr.spec, pnr.placement)
    assert res == 4


def test_schedule_dependence_windows_hold(gaussian_sim):
    """Re-derive every producer->consumer arrival and check the modulo
    hold window independently of the scheduler's own _check."""
    app, dp, mapping, prog, pnr = gaussian_sim
    s = prog.schedule
    coords = pnr.placement.coords
    cells = pnr.netlist.cells
    routed = {n.name: n for n in pnr.routes.nets}
    hold = s.latch_depth * s.ii
    for net in pnr.netlist.nets:
        nt = route_timing(routed[net.name])
        drv = cells[net.driver]
        src = (("pe", drv.instance) if drv.kind == "pe"
               else ("in", net.signal))
        for sink in net.sinks:
            if cells[sink].kind != "pe":
                continue
            arr = s.start[src] + L_OUT + nt.depth[coords[sink]]
            t = s.start[("pe", cells[sink].instance)]
            assert arr + L_LATCH <= t <= arr + hold, (net.name, sink)


# ---------------------------------------------------------------------------
# golden verification: sim == interp, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["gaussian", "harris", "ds"])
def test_sim_bit_matches_interp(name):
    apps = {**image_graphs(), **ml_graphs()}
    app = apps[name]
    dp, mapping = _flow(name, app)
    report = verify_mapping(dp, mapping, app, SPEC, iterations=3, batch=2,
                            **FAST)
    assert report.bit_exact and report.max_abs_err == 0.0, report.row()
    assert report.ii >= report.min_ii


@pytest.mark.slow
@pytest.mark.parametrize("name", ["camera", "laplacian", "conv", "block",
                                  "strc"])
def test_sim_bit_matches_interp_full_suite(name):
    apps = {**image_graphs(), **ml_graphs()}
    app = apps[name]
    dp, mapping = _flow(name, app)
    report = verify_mapping(dp, mapping, app, SPEC, iterations=3, batch=2,
                            place_backend="jax", chains=4, sweeps=16)
    assert report.bit_exact and report.max_abs_err == 0.0, report.row()


def test_sim_multiop_merged_variant_bit_matches():
    """Merged PE variants produce multi-op instances (intra-tile temps)."""
    from repro.core import MiningConfig
    from repro.core.dse import build_variants, mine_and_rank

    app = image_graphs()["gaussian"]
    cfg = MiningConfig(min_support=3, max_pattern_nodes=5, time_budget_s=10,
                       max_patterns_per_level=30)
    variants = build_variants("gaussian", app, mine_and_rank(app, cfg),
                              max_merge=2)
    assert len(variants) >= 2
    v = variants[-1]
    mapping = map_application(v.datapath, app, "gaussian")
    assert max(i.n_ops for i in mapping.instances) >= 2
    prog, _ = build_sim(v.datapath, mapping, app, SPEC, **FAST)
    inputs = random_inputs(prog, 2, 2, seed=7)
    _, err, exact = check_against_interp(prog, app, inputs)
    assert exact and err == 0.0


def test_sim_pallas_backend_matches_jax(gaussian_sim):
    app, dp, mapping, prog, pnr = gaussian_sim
    inputs = random_inputs(prog, 2, 1, seed=3)
    res_jax, err_jax, exact_jax = check_against_interp(prog, app, inputs,
                                                       backend="jax")
    res_pl, err_pl, exact_pl = check_against_interp(prog, app, inputs,
                                                    backend="pallas")
    assert exact_jax and exact_pl and err_jax == err_pl == 0.0
    assert np.array_equal(res_jax.outputs, res_pl.outputs)


def test_simulate_accepts_dict_and_array_inputs(gaussian_sim):
    app, dp, mapping, prog, pnr = gaussian_sim
    arr = random_inputs(prog, 2, 2, seed=5)
    by_name = {name: arr[:, :, j]
               for j, name in enumerate(prog.input_names)}
    a = simulate(prog, arr)
    b = simulate(prog, by_name)
    assert np.array_equal(a.outputs, b.outputs)
    assert a.outputs.shape == (2, 2, len(app.outputs))
    assert a.cycles == prog.total_cycles(2)
    assert 0 < a.active_frac <= 1.0


# The hypothesis property test (random graphs -> sim == interp) lives in
# tests/test_property.py with the other importorskip-guarded properties.


# ---------------------------------------------------------------------------
# kernels: tile-step dispatch backends agree
# ---------------------------------------------------------------------------
def test_alu_step_backends_agree():
    from repro.kernels.sim_step import (alu_step_jnp, alu_step_pallas,
                                        alu_step_reference, op_table)

    ops = op_table(["add", "sub", "mul", "min", "max", "sel", "ashr",
                    "gt", "abs"])
    rng = np.random.default_rng(11)
    n, b = 37, 5
    codes = rng.integers(0, len(ops), n).astype(np.int32)
    a = rng.standard_normal((b, n)).astype(np.float32)
    # integral second operands: shift amounts are 2**b, and libm vs XLA
    # pow only agree bit-exactly on integral exponents (as in the apps,
    # where shifts come from constant registers)
    bb = rng.integers(-3, 4, (b, n)).astype(np.float32)
    c = rng.standard_normal((b, n)).astype(np.float32)
    want = alu_step_reference(codes, a, bb, c, ops)
    got_jnp = np.asarray(alu_step_jnp(codes, a, bb, c, ops))
    got_pl = np.asarray(alu_step_pallas(codes, a, bb, c, ops,
                                        interpret=True))
    assert np.array_equal(got_jnp, want)
    assert np.array_equal(got_pl, want)


def test_alu_step_rejects_unknown_ops():
    from repro.kernels.sim_step import op_table

    with pytest.raises(NotImplementedError):
        op_table(["add", "matmul"])


# ---------------------------------------------------------------------------
# FabricOptions blobs: the removed hpwl_backend field
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kernel, reads", [("jnp", True), ("pallas", False)])
def test_fabric_options_blob_with_removed_hpwl_backend(kernel, reads):
    from repro.errors import ConfigFormatError

    blob = FabricOptions(spec=SPEC, chains=3).to_dict()
    assert "hpwl_backend" not in blob
    old = blob | {"hpwl_backend": kernel}
    if reads:
        assert FabricOptions.from_dict(old) == FabricOptions.from_dict(blob)
    else:
        with pytest.raises(ConfigFormatError, match="'hpwl_backend'") as e:
            FabricOptions.from_dict(old)
        assert "\n" not in str(e.value)


# ---------------------------------------------------------------------------
# DSE integration: FabricOptions + simulate=True
# ---------------------------------------------------------------------------
def test_fabric_options_coerce_legacy_kwargs():
    opts = FabricOptions.coerce(SPEC, backend="python", chains=3, sweeps=9,
                                seed=2, simulate=True)
    assert opts.spec == SPEC and opts.backend == "python"
    assert opts.chains == 3 and opts.sweeps == 9 and opts.simulate
    assert FabricOptions.coerce(None) is None
    with pytest.raises(ValueError):
        FabricOptions.coerce(None, simulate=True)
    with pytest.raises(TypeError):
        FabricOptions.coerce("8x8")
    # passing an options object through is idempotent
    again = FabricOptions.coerce(opts)
    assert again == opts
    # mixing an options object with non-default legacy kwargs is an error,
    # not a silent discard
    with pytest.raises(ValueError, match="legacy kwargs"):
        FabricOptions.coerce(opts, chains=64)


def test_dse_simulate_records_measured_throughput():
    app = image_graphs()["gaussian"]
    dp = baseline_datapath(app_ops(app))
    v = PEVariant("PE1", dp)
    evaluate_variants([v], {"gaussian": app},
                      fabric=FabricOptions(spec=SPEC, backend="python",
                                           chains=1, sweeps=8,
                                           simulate=True))
    c = v.costs["gaussian"]
    assert c.sim_ii >= c.sim_min_ii >= 1
    assert c.sim_verified == 1                 # bit-exact golden check ran
    assert c.sim_latency_cycles > 0
    assert c.sim_active_frac == pytest.approx(1.0 / c.sim_ii)
    assert c.sim_throughput_gops > 0
    # idle cycles make measured energy/op dominate the static array number
    assert c.sim_energy_per_op_pj > c.fabric_energy_per_op_pj


def test_dse_legacy_fabric_kwargs_still_work():
    app = image_graphs()["gaussian"]
    dp = baseline_datapath(app_ops(app))
    v = PEVariant("PE1", dp)
    evaluate_variants([v], {"gaussian": app}, fabric=SPEC,
                      fabric_backend="python", fabric_chains=1,
                      fabric_sweeps=8)
    c = v.costs["gaussian"]
    assert c.fabric_energy_per_op_pj > 0
    assert c.sim_ii == 0                       # simulate not requested
