"""Fabric PnR benchmark: placer scaling sweep (delta vs full move scoring),
the JAX-batched vs Python-chain comparison, and router/HPWL microbenches.

The headline table anneals synthetic netlists that fill 8x8 .. 64x64
fabrics with both ``score_mode="delta"`` (incremental rescoring of only
the nets a swap touches) and ``score_mode="full"`` (recompute all N nets
per move), verifies the two modes return bit-identical placements, and
reports the per-sweep speedup — the number that bounds how much design
space the DSE loop can sweep.  Each timed anneal is re-run ``--repeats N``
times (default 3 at full budget, 1 in smoke) and the report carries the
median plus a median/IQR ``repeats`` sub-block per size — never a lone
wall-clock.  Results land in machine-readable ``results/BENCH_pnr.json``
(schema ``pnr_bench/v2``, with an embedded run manifest) so the perf
trajectory is tracked across PRs by ``python -m repro.obs.regress``;
acceptance floor is a >=5x speedup at 32x32 plus a completed 64x64 anneal.

The ``hier`` section times the two-level hierarchical flow
(:func:`repro.fabric.place.place_hierarchical`) against the flat anneal
on locality-structured mega-fabric netlists (64x64 and 128x128; 256x256
with ``--mega``, the nightly budget), asserts delta-vs-full bit-identity
at *every level* (cluster, detail, deblock), and records the
hierarchical-vs-flat wall-clock ratio — the number that opens the
>=128x128 regime the flat annealer cannot reach.

Run:  PYTHONPATH=src python -m benchmarks.pnr_bench \
          [--smoke] [--mega] [--repeats N] [--out P]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import numpy as np

from repro.apps import image_graphs
from repro.core import baseline_datapath, map_application
from repro.core.dse import app_ops
from repro.fabric import (FabricSpec, extract_netlist, lower, place,
                          place_hierarchical, route_nets, synthetic_netlist)
from repro.fabric.place import anneal_jax_batch, anneal_python

from .common import emit, manifest_block, repeats_block

DEFAULT_OUT = os.path.join("results", "BENCH_pnr.json")
SWEEPS = 24
CHAIN_COUNTS = (1, 8, 32)
SCALE_SIZES = (8, 16, 32, 64)
#: timing budget for the scaling sweep — per-sweep cost is what a DSE
#: evaluation pays, so a short fixed budget at a fixed seed is enough
SCALE_SWEEPS = 2
SCALE_CHAINS = 1
#: hierarchical sweep: sizes the committed report carries; 256x256 is the
#: nightly (--mega) budget, flat comparison stops at HIER_FLAT_MAX
HIER_SIZES = (64, 128)
HIER_MEGA_SIZE = 256
HIER_FLAT_MAX = 128
#: sink-window radius for the synthetic mega netlists — real mapped
#: dataflow is local; without it there are no clusters to find
HIER_LOCALITY = 4


def _anneal(problem, **kw):
    """(slots, costs) of one problem's chains, annealed as a group of one."""
    (slots, costs), = anneal_jax_batch([problem], **kw)
    return slots, costs


def _timed_anneal(problem, score_mode: str, *, chains: int, sweeps: int,
                  seed: int, repeats: int = 1):
    """(wall-second samples, slots, costs) for steady-state annealer calls.

    Each repeat re-runs the already-compiled program on the same seed, so
    the samples measure dispatch+run noise while slots/costs stay
    bit-identical across repeats.
    """
    _anneal(problem, chains=chains, seed=seed, sweeps=sweeps,
            score_mode=score_mode)                      # trace + compile
    samples = []
    slots = costs = None
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        slots, costs = _anneal(problem, chains=chains, seed=seed + 1,
                               sweeps=sweeps, score_mode=score_mode)
        samples.append(time.perf_counter() - t0)
    return samples, slots, costs


def scaling_sweep(sizes=SCALE_SIZES, *, sweeps: int = SCALE_SWEEPS,
                  chains: int = SCALE_CHAINS, seed: int = 4,
                  repeats: int = 1) -> list:
    """Anneal synthetic netlists at each size in both score modes."""
    records = []
    for size in sizes:
        spec = FabricSpec(rows=size, cols=size)
        problem = lower(synthetic_netlist(spec, seed=seed), spec)
        rec = {"rows": size, "cols": size,
               "n_cells": problem.n_pe_cells + problem.n_io_cells,
               "n_nets": int(np.count_nonzero(
                   problem.net_mask.any(axis=1))),
               "sweeps": sweeps, "chains": chains}
        s_d, slots_d, costs_d = _timed_anneal(
            problem, "delta", chains=chains, sweeps=sweeps, seed=seed,
            repeats=repeats)
        s_f, slots_f, costs_f = _timed_anneal(
            problem, "full", chains=chains, sweeps=sweeps, seed=seed,
            repeats=repeats)
        dt_d = statistics.median(s_d)
        dt_f = statistics.median(s_f)
        rec["delta_wall_s"] = dt_d
        rec["full_wall_s"] = dt_f
        rec["delta_us_per_sweep"] = dt_d * 1e6 / sweeps
        rec["full_us_per_sweep"] = dt_f * 1e6 / sweeps
        rec["speedup"] = dt_f / dt_d
        rec["repeats"] = repeats_block(
            {"delta_wall_s": s_d, "full_wall_s": s_f}, repeats)
        rec["delta_hpwl"] = float(np.min(costs_d))
        rec["full_hpwl"] = float(np.min(costs_f))
        rec["bit_identical"] = bool(np.array_equal(slots_d, slots_f)
                                    and np.array_equal(costs_d, costs_f))
        # the smoke step's whole point: a delta/full divergence must fail
        # the run (and CI), not just record False in the report
        assert rec["bit_identical"], (
            f"score_mode divergence at {size}x{size}: delta returned "
            f"hpwl={rec['delta_hpwl']}, full {rec['full_hpwl']}")
        records.append(rec)
        emit(f"pnr_scale_{size}x{size}_delta", dt_d * 1e6 / sweeps,
             f"hpwl={rec['delta_hpwl']:.0f};cells={rec['n_cells']}")
        emit(f"pnr_scale_{size}x{size}_full", dt_f * 1e6 / sweeps,
             f"hpwl={rec['full_hpwl']:.0f};"
             f"speedup={rec['speedup']:.2f}x;"
             f"identical={rec['bit_identical']}")
    return records


def anneal_64x64(*, chains: int = 2, sweeps: int = 8, seed: int = 4,
                 repeats: int = 1) -> dict:
    """A realistic-budget 64x64 anneal — only feasible with delta scoring;
    records the completed run the ROADMAP scaling item asks for."""
    spec = FabricSpec(rows=64, cols=64)
    problem = lower(synthetic_netlist(spec, seed=seed), spec)
    t0 = time.perf_counter()
    _anneal(problem, chains=chains, seed=seed, sweeps=sweeps,
            score_mode="delta")                         # trace + compile
    compile_s = time.perf_counter() - t0
    samples = []
    costs = None
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        _, costs = _anneal(problem, chains=chains, seed=seed + 1,
                           sweeps=sweeps, score_mode="delta")
        samples.append(time.perf_counter() - t0)
    wall = statistics.median(samples)
    rec = {"rows": 64, "cols": 64, "chains": chains, "sweeps": sweeps,
           "score_mode": "delta", "wall_s": wall,
           "compile_and_first_run_s": compile_s,
           "repeats": repeats_block({"wall_s": samples}, repeats),
           "n_cells": problem.n_pe_cells + problem.n_io_cells,
           "best_hpwl": float(np.min(costs)), "completed": True}
    emit("pnr_anneal_64x64_delta", wall * 1e6,
         f"best_hpwl={rec['best_hpwl']:.0f};sweeps={sweeps}x{chains}ch")
    return rec


def _hier_levels_identical(a, b) -> dict:
    """Per-level delta-vs-full comparison of two HierPlacements."""
    return {
        "cluster": bool(np.array_equal(a.cluster_slots, b.cluster_slots)),
        "detail": bool(set(a.detail_slots) == set(b.detail_slots)
                       and all(np.array_equal(a.detail_slots[k],
                                              b.detail_slots[k])
                               for k in a.detail_slots)),
        "deblock": bool((a.deblock_slots is None) == (b.deblock_slots is None)
                        and (a.deblock_slots is None
                             or np.array_equal(a.deblock_slots,
                                               b.deblock_slots))),
        "final": bool(a.coords == b.coords and a.cost == b.cost),
    }


def hier_sweep(sizes=HIER_SIZES, *, chains: int = 2, sweeps: int = 2,
               seed: int = 4, repeats: int = 1,
               flat_max: int = HIER_FLAT_MAX) -> list:
    """Time hierarchical vs flat placement on locality-structured
    netlists; assert per-level delta/full bit-identity at every size."""
    records = []
    for size in sizes:
        spec = FabricSpec(rows=size, cols=size)
        nl = synthetic_netlist(spec, seed=seed, locality=HIER_LOCALITY)

        def hier(score_mode):
            return place_hierarchical(nl, spec, chains=chains,
                                      sweeps=sweeps, seed=seed + 1,
                                      score_mode=score_mode)

        # bit-identity first — these runs also compile both programs
        hd, hf = hier("delta"), hier("full")
        levels = _hier_levels_identical(hd, hf)
        assert all(levels.values()), (
            f"hierarchical score_mode divergence at {size}x{size}: "
            f"{levels}")
        rec = {"rows": size, "cols": size, "chains": chains,
               "sweeps": sweeps, "cluster_grid": hd.cluster_grid,
               "n_cells": len(nl.pe_cells) + len(nl.io_cells),
               "n_nets": len(nl.nets),
               "detail_dispatches": hd.detail_dispatches,
               "hier_hpwl": hd.cost,
               "bit_identical_levels": levels}
        s_h = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            hier("delta")
            s_h.append(time.perf_counter() - t0)
        rec["hier_wall_s"] = statistics.median(s_h)
        samples = {"hier_wall_s": s_h}
        if size <= flat_max:
            place(nl, spec, backend="jax", chains=chains, sweeps=sweeps,
                  seed=seed + 1, score_mode="delta")      # trace + compile
            s_f = []
            flat_pl = None
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                flat_pl = place(nl, spec, backend="jax", chains=chains,
                                sweeps=sweeps, seed=seed + 1,
                                score_mode="delta")
                s_f.append(time.perf_counter() - t0)
            rec["flat_wall_s"] = statistics.median(s_f)
            rec["flat_hpwl"] = flat_pl.cost
            rec["speedup_vs_flat"] = rec["flat_wall_s"] / rec["hier_wall_s"]
            samples["flat_wall_s"] = s_f
        rec["repeats"] = repeats_block(samples, repeats)
        rec["completed"] = True
        records.append(rec)
        emit(f"pnr_hier_{size}x{size}", rec["hier_wall_s"] * 1e6,
             f"hpwl={hd.cost:.0f};grid={hd.cluster_grid};"
             + (f"vs_flat={rec['speedup_vs_flat']:.2f}x"
                if "speedup_vs_flat" in rec else "flat=skipped"))
    return records


def hier_cluster1_check(size: int = 32, *, chains: int = 2,
                        sweeps: int = 2, seed: int = 4) -> dict:
    """cluster_grid=1 must reproduce the flat placer bit-for-bit."""
    spec = FabricSpec(rows=size, cols=size)
    nl = synthetic_netlist(spec, seed=seed, locality=HIER_LOCALITY)
    flat = place(nl, spec, backend="jax", chains=chains, sweeps=sweeps,
                 seed=seed, score_mode="delta")
    h1 = place_hierarchical(nl, spec, cluster_grid=1, chains=chains,
                            sweeps=sweeps, seed=seed, score_mode="delta")
    identical = bool(h1.coords == flat.coords and h1.cost == flat.cost
                     and h1.chain_costs == flat.chain_costs)
    assert identical, (
        f"cluster_grid=1 diverged from flat at {size}x{size}: "
        f"{h1.cost} vs {flat.cost}")
    emit(f"pnr_hier_cluster1_{size}x{size}", 0.0,
         f"identical={identical}")
    return {"rows": size, "cols": size, "cluster1_identical": identical}


def _harris_problem():
    app = image_graphs()["harris"]
    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, "harris")
    spec = FabricSpec(rows=8, cols=8)
    netlist = extract_netlist(mapping, app, spec)
    return dp, mapping, app, spec, netlist


def harris_bench() -> dict:
    """The original harris-app comparison: python chains vs batched JAX,
    router timing, and the batched-HPWL microkernel."""
    dp, mapping, app, spec, netlist = _harris_problem()
    problem = lower(netlist, spec)
    out = {"python_us_per_chain": {}, "jax_us_per_chain": {}}

    # -- python single-chain reference, run `chains` times sequentially ----
    for chains in CHAIN_COUNTS:
        t0 = time.perf_counter()
        costs = [anneal_python(problem, seed=c, sweeps=SWEEPS)[1]
                 for c in range(chains)]
        dt = (time.perf_counter() - t0) * 1e6
        out["python_us_per_chain"][chains] = dt / chains
        emit(f"pnr_anneal_python_c{chains}", dt / chains,
             f"best_hpwl={min(costs):.0f}")

    # -- jax batched chains (first call includes trace+compile; report the
    # steady-state second call, which is what a DSE sweep pays) ------------
    for chains in CHAIN_COUNTS:
        _anneal(problem, chains=chains, seed=0, sweeps=SWEEPS)  # warmup
        t0 = time.perf_counter()
        _, costs = _anneal(problem, chains=chains, seed=1, sweeps=SWEEPS)
        dt = (time.perf_counter() - t0) * 1e6
        out["jax_us_per_chain"][chains] = dt / chains
        emit(f"pnr_anneal_jax_c{chains}", dt / chains,
             f"best_hpwl={float(np.min(costs)):.0f}")

    for chains in CHAIN_COUNTS:
        emit(f"pnr_jax_speedup_c{chains}", out["jax_us_per_chain"][chains],
             f"python/jax={out['python_us_per_chain'][chains] / out['jax_us_per_chain'][chains]:.2f}x")

    # -- router ------------------------------------------------------------
    placement = place(netlist, spec, backend="jax", chains=8, sweeps=SWEEPS)
    t0 = time.perf_counter()
    routes = route_nets(netlist, placement, spec)
    dt = (time.perf_counter() - t0) * 1e6
    out["route_us"] = dt
    emit("pnr_route_harris", dt,
         f"wl={routes.wirelength};overflow={routes.overflow}")

    # -- HPWL kernel microbenchmark ---------------------------------------
    from repro.kernels.pnr_cost import hpwl_batched

    rng = np.random.default_rng(0)
    n_ent = problem.n_entities
    pos = problem.slot_xy[
        np.stack([rng.permutation(n_ent) for _ in range(256)])]
    pins = problem.net_pins
    mask = problem.net_mask
    hpwl_batched(pos, pins, mask).block_until_ready()      # warmup
    t0 = time.perf_counter()
    hpwl_batched(pos, pins, mask).block_until_ready()
    dt = (time.perf_counter() - t0) * 1e6
    out["hpwl_batched_256_us"] = dt
    emit("pnr_hpwl_batched_256", dt, f"nets={pins.shape[0]}")
    return out


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats=None, mega: bool = False) -> dict:
    import jax

    if repeats is None:
        repeats = 1 if smoke else 3
    repeats = max(1, int(repeats))
    report = {"schema": "pnr_bench/v3",
              "host_backend": jax.default_backend(),
              "smoke": smoke,
              "manifest": manifest_block(),
              "repeats": {"n": repeats}}
    if smoke:
        # CI smoke: 8x8, 2 sweeps, both score modes — proves the delta and
        # full programs still agree and keeps a perf datapoint per PR;
        # plus one tiny hierarchical placement with its level-identity and
        # cluster_grid=1 == flat gates
        report["sizes"] = scaling_sweep((8,), sweeps=2, repeats=repeats)
        report["hier"] = hier_sweep((32,), repeats=repeats)
        report["hier_cluster1"] = hier_cluster1_check(32)
    else:
        report["sizes"] = scaling_sweep(repeats=repeats)
        report["anneal64"] = anneal_64x64(repeats=repeats)
        report["harris"] = harris_bench()
        sizes = HIER_SIZES + ((HIER_MEGA_SIZE,) if mega else ())
        report["hier"] = hier_sweep(sizes, repeats=repeats)
        report["hier_cluster1"] = hier_cluster1_check(32)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    emit("pnr_bench_json", 0.0, f"path={out_path}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--smoke", action="store_true",
                    help="8x8 only, 2 sweeps, both score modes (CI step)")
    ap.add_argument("--mega", action="store_true",
                    help="add the 256x256 hierarchical placement "
                         "(nightly budget)")
    ap.add_argument("--repeats", type=int, default=None, metavar="N",
                    help="timed repeats per anneal (default: 3 full, "
                         "1 smoke); the report records median + IQR")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.out, smoke=args.smoke, repeats=args.repeats, mega=args.mega)


if __name__ == "__main__":
    main()
