"""Batched-vs-serial stage benchmarks on the Fig. 11 ML suite.

Default mode — the pnr stage: a pair placed alone
(:func:`repro.fabric.place_and_route`, the deprecated drivers' path)
costs one device dispatch per pair.  The Explorer's ``pnr`` stage
gathers all pairs, pads them to bucket shapes, and anneals every
bucket-compatible group's chains in ONE JAX dispatch.

``--simulate`` — the schedule/simulate stages: the per-pair loop runs the
modulo scheduler one pair at a time in Python and compiles one
``lax.scan`` per program; the batch-first stages advance all pairs'
schedulers in lockstep (stacked slot-conflict scans) and run every
bucket-compatible group of programs through ONE vmapped scan
(``sim_batch="grouped"``), with bit-identical schedules and outputs.

Both modes run from a shared upstream store (everything upstream of the
stage under test is already done) and from cold compile caches (a fresh
exploration's real cost).  ``--repeats N`` (default 3 at full budget, 1
in smoke) re-runs each timed stage N times — the memo store is purged
between repeats via ``Explorer.forget`` — and the artifact records the
**median** wall-clock plus a median/IQR ``repeats`` block, never a lone
sample.  Every artifact also embeds a run ``manifest`` (git SHA,
versions, device, XLA-cache state) and memory gauges (per-stage host
peak + live device bytes, measured on a separate untimed telemetry
pass).  Results land in ``results/BENCH_explore.json`` /
``results/BENCH_sim_batch.json`` (committed + CI artifact + gated by
``results/check_bench.py`` and tracked by ``python -m
repro.obs.regress``).

Run:  PYTHONPATH=src python -m benchmarks.explore_bench \
          [--simulate] [--smoke] [--repeats N] [--out P]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

from repro.apps import ml_graphs
from repro.explore import ExploreConfig, Explorer
from repro.fabric import FabricOptions, FabricSpec

from .common import (BENCH_MINING, FAST_MINING, emit, manifest_block,
                     repeats_block)

DEFAULT_OUT = os.path.join("results", "BENCH_explore.json")
DEFAULT_SIM_OUT = os.path.join("results", "BENCH_sim_batch.json")


def _write(result: dict, out_path: str) -> None:
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


def _default_repeats(smoke: bool, repeats) -> int:
    """Nightly/full runs default to median-of-3; smoke stays single-shot
    (its assertions are ratios, and CI minutes are budgeted)."""
    if repeats is not None:
        return max(1, int(repeats))
    return 1 if smoke else 3


def _counter_snapshot(registry) -> dict:
    return dict(registry.to_dict()["counters"])


def _metrics_block(registry, before: dict, keys) -> dict:
    """Registry counter deltas for the BENCH json ``metrics`` block.

    Keys must stay inside ``results/check_bench.py``'s METRIC_KEYS
    contract; dotted counter families (``memo.hit.*`` -> ``memo_hit``)
    are summed.  The gate cross-checks the dispatch entries against the
    top-level claims, so these numbers are the registry speaking, not a
    hand-maintained copy.
    """
    after = _counter_snapshot(registry)
    families = {"memo_hit": "memo.hit", "memo_miss": "memo.miss",
                "compile_events": "jax.compile.events"}
    block = {}
    for key in keys:
        prefix = families.get(key, key)
        block[key] = sum(v - before.get(k, 0) for k, v in after.items()
                         if k == prefix or k.startswith(prefix + "."))
    return block


def _memory_gauges(registry, stages) -> dict:
    """Max per-stage host-peak / device-byte gauges (set by the untimed
    telemetry pass) in METRIC_KEYS shape."""
    gauges = registry.to_dict()["gauges"]

    def peak(prefix):
        vals = [v for k, v in gauges.items()
                if k.startswith(prefix) and k.split(".")[-1] in stages
                and isinstance(v, (int, float))]
        return int(max(vals)) if vals else 0

    return {"host_peak_bytes": peak("mem.host_peak_bytes."),
            "device_bytes": peak("mem.device_bytes.")}


def _memory_pass(base, stages, run_fn) -> None:
    """One untimed instrumented run: telemetry on (tracemalloc spans +
    device-byte gauges), compile caches warm from the timed repeats, so
    this measures footprint without polluting the wall-clock samples."""
    from repro import obs
    obs.enable_telemetry()
    try:
        base.forget(*stages)
        run_fn()
    finally:
        obs.enable_telemetry(False)


def run(out_path: str = DEFAULT_OUT, smoke: bool = False,
        repeats=None) -> dict:
    repeats = _default_repeats(smoke, repeats)
    apps = ml_graphs()
    fabric = FabricOptions(
        spec=FabricSpec(rows=16, cols=16), backend="jax",
        chains=4 if smoke else 8, sweeps=8 if smoke else 24)
    cfg = ExploreConfig(mode="per_app",
                        mining=FAST_MINING if smoke else BENCH_MINING,
                        max_merge=2 if smoke else 3, fabric=fabric)

    # shared upstream artifacts: both modes see identical mappings
    base = Explorer(apps, cfg)
    base.map()
    from repro.obs import jaxprof
    jaxprof.enable(registry=base.metrics)

    from repro.explore.pipeline import _pair_nonce, _pnr_pair
    import importlib
    # repro.fabric re-exports the place() *function*, shadowing the
    # submodule attribute — resolve the module explicitly
    place_mod = importlib.import_module("repro.fabric.place")

    def timed_serial():
        # one dispatch per pair, each placed alone as place_and_route
        # places it, from fresh annealer programs
        place_mod._build_batch_annealer.cache_clear()
        datapaths = {(v.name, a): v.datapath
                     for a, variants in base.merge().items()
                     for v in variants}
        t0 = time.perf_counter()
        pnrs = {pair: _pnr_pair(pair[0], datapaths[pair], mapping,
                                apps[pair[1]], fabric, _pair_nonce(*pair))
                for pair, mapping in base.map().items()}
        return time.perf_counter() - t0, pnrs, len(pnrs)

    def timed_grouped():
        # fresh annealer programs + a purged pnr memo per repeat (cold
        # caches emulate a fresh exploration)
        place_mod._build_batch_annealer.cache_clear()
        base.forget("pnr")
        before = base.stats["pnr_dispatch"]
        t0 = time.perf_counter()
        pnrs = base.pnr()
        dt = time.perf_counter() - t0
        failures.extend(base.failures)        # clean-run proof: see the
        return dt, pnrs, base.stats["pnr_dispatch"] - before  # failures

    samples = {"serial_s": [], "grouped_s": []}
    failures: list = []
    serial_pnrs = serial_disp = None
    for _ in range(repeats):
        dt, serial_pnrs, serial_disp = timed_serial()
        samples["serial_s"].append(dt)
    grouped_pnrs = grouped_disp = None
    before = None
    for _ in range(repeats):
        before = _counter_snapshot(base.metrics)   # last repeat's deltas
        dt, grouped_pnrs, grouped_disp = timed_grouped()
        samples["grouped_s"].append(dt)
    metrics = _metrics_block(base.metrics, before,
                             ("pnr_dispatch", "memo_miss", "memo_hit",
                              "compile_events"))
    _memory_pass(base, ("pnr",), base.pnr)
    metrics.update(_memory_gauges(base.metrics, ("pnr",)))
    jaxprof.disable()

    pairs = len(serial_pnrs)
    assert len(grouped_pnrs) == pairs
    # both modes must produce equally valid arrays: every net routed on a
    # legally fitted grid (place_and_route refuses anything else)
    for pnrs in (serial_pnrs, grouped_pnrs):
        for pnr in pnrs.values():
            assert pnr.routes.success, "routing overflow in benchmark run"

    serial_s = statistics.median(samples["serial_s"])
    grouped_s = statistics.median(samples["grouped_s"])
    speedup = serial_s / max(grouped_s, 1e-9)
    result = {
        "bench": "explore_pnr_batch",
        "suite": "fig11_ml@16x16",
        "mode": "smoke" if smoke else "full",
        "manifest": manifest_block(),
        "pairs": pairs,
        "chains": fabric.chains,
        "sweeps": fabric.sweeps,
        "serial_dispatches": serial_disp,
        "grouped_dispatches": grouped_disp,
        "serial_s": round(serial_s, 3),
        "grouped_s": round(grouped_s, 3),
        "speedup": round(speedup, 2),
        "repeats": repeats_block(samples, repeats),
        # registry deltas for the grouped run — check_bench.py asserts
        # pnr_dispatch agrees with grouped_dispatches above
        "metrics": metrics,
        # check_bench.py rejects artifacts measured on degraded runs
        "failures": [f.to_dict() for f in failures],
        "note": "pnr stage only, shared upstream artifacts, cold annealer "
                "caches per repeat (includes jit compiles — the cost of a "
                "fresh exploration); wall-clocks are medians over repeats",
    }
    _write(result, out_path)

    emit("explore_pnr_serial", serial_s * 1e6,
         f"pairs={pairs};dispatches={result['serial_dispatches']}")
    emit("explore_pnr_grouped", grouped_s * 1e6,
         f"pairs={pairs};dispatches={result['grouped_dispatches']}")
    emit("explore_pnr_speedup", grouped_s * 1e6,
         f"{speedup:.2f}x (target >=3x);repeats={repeats};out={out_path}")
    if smoke:
        assert speedup > 1.0, (
            f"batched pnr slower than serial ({speedup:.2f}x)")
    return result


def run_sim(out_path: str = DEFAULT_SIM_OUT, smoke: bool = False,
            repeats=None) -> dict:
    """Schedule+simulate stages, serial vs grouped, from shared pnr."""
    import numpy as np

    from repro.explore.pipeline import _pair_nonce
    from repro.sim import random_inputs, sim_signature, simulate, \
        simulate_batch
    from repro.sim import cycle as cycle_mod

    repeats = _default_repeats(smoke, repeats)
    apps = ml_graphs()
    fabric = FabricOptions(
        spec=FabricSpec(rows=16, cols=16), backend="jax",
        chains=4 if smoke else 8, sweeps=8 if smoke else 24, simulate=True)
    cfg = ExploreConfig(mode="per_app",
                        mining=FAST_MINING if smoke else BENCH_MINING,
                        max_merge=2 if smoke else 3, fabric=fabric)

    # shared upstream artifacts: both modes schedule the same placements
    base = Explorer(apps, cfg)
    base.pnr()
    from repro.obs import jaxprof
    jaxprof.enable(registry=base.metrics)

    def timed(sim_batch: str):
        # cold compile caches + purged sched/sim memo per repeat emulate
        # a fresh exploration; the sched/sim memo keys include sim_batch,
        # so each mode works from scratch
        cycle_mod._build_batch_stepper.cache_clear()
        base.forget("sched", "sim")
        ex = base.with_config(sim_batch=sim_batch)
        d0 = {k: ex.stats[k] for k in ("sim_dispatch", "sched_group")}
        t0 = time.perf_counter()
        progs = ex.schedule()
        flags = ex.simulate()
        dt = time.perf_counter() - t0
        failures.extend(ex.failures)          # clean-run proof
        return dt, progs, flags, {k: ex.stats[k] - d0[k] for k in d0}

    samples = {"serial_s": [], "grouped_s": []}
    failures: list = []
    serial_progs = serial_flags = None
    for _ in range(repeats):
        dt, serial_progs, serial_flags, _d = timed("serial")
        samples["serial_s"].append(dt)
    grouped_progs = grouped_flags = disp = None
    before = None
    for _ in range(repeats):
        before = _counter_snapshot(base.metrics)   # last repeat's deltas
        dt, grouped_progs, grouped_flags, disp = timed("grouped")
        samples["grouped_s"].append(dt)
    metrics_blk = _metrics_block(
        base.metrics, before,
        ("sim_dispatch", "sched_group", "sched_rounds", "sched_backtracks",
         "memo_miss", "memo_hit", "compile_events"))

    def sim_pass():
        ex = base.with_config(sim_batch="grouped")
        ex.schedule()
        ex.simulate()

    _memory_pass(base, ("sched", "sim"), sim_pass)
    metrics_blk.update(_memory_gauges(base.metrics,
                                      ("schedule", "simulate")))
    jaxprof.disable()

    pairs = sorted(serial_progs)
    assert sorted(grouped_progs) == pairs
    # both modes bit-exact against the interpreter on the same
    # nonce-seeded vectors (sim_verify raises otherwise) ...
    verified = (all(serial_flags[p] == 1 for p in pairs)
                and all(grouped_flags[p] == 1 for p in pairs))
    # ... and the achieved schedules are identical
    ii_identical = all(serial_progs[p].ii == grouped_progs[p].ii
                       and serial_progs[p].latency == grouped_progs[p].latency
                       for p in pairs)
    # direct bit-compare of the two modes' simulated outputs (the serial
    # steppers and the grouped bucket programs are already compiled, so
    # this re-run is cheap)
    K, B = fabric.sim_iterations, fabric.sim_batch
    inputs = {p: random_inputs(serial_progs[p], K, B,
                               seed=fabric.input_seed(_pair_nonce(*p)))
              for p in pairs}
    by_bucket = {}
    for p in pairs:
        sig = sim_signature(grouped_progs[p], K, B)
        by_bucket.setdefault(sig, []).append(p)
    bit_identical = True
    for members in by_bucket.values():
        batch = simulate_batch([grouped_progs[p] for p in members],
                               [inputs[p] for p in members])
        for p, res in zip(members, batch):
            ref = simulate(serial_progs[p], inputs[p])
            bit_identical &= bool(np.array_equal(res.outputs, ref.outputs))

    serial_s = statistics.median(samples["serial_s"])
    grouped_s = statistics.median(samples["grouped_s"])
    speedup = serial_s / max(grouped_s, 1e-9)
    result = {
        "bench": "explore_sim_batch",
        "suite": "fig11_ml@16x16",
        "mode": "smoke" if smoke else "full",
        "manifest": manifest_block(),
        "pairs": len(pairs),
        "sim_iterations": K,
        "sim_input_batch": B,
        "serial_compiles": len(pairs),
        "grouped_sim_dispatches": disp["sim_dispatch"],
        "grouped_sched_groups": disp["sched_group"],
        "serial_s": round(serial_s, 3),
        "grouped_s": round(grouped_s, 3),
        "speedup": round(speedup, 2),
        "repeats": repeats_block(samples, repeats),
        "bit_identical": bit_identical,
        "ii_identical": ii_identical,
        "verified": verified,
        # registry deltas for the grouped run — check_bench.py asserts the
        # dispatch/group entries agree with the claims above
        "metrics": metrics_blk,
        # check_bench.py rejects artifacts measured on degraded runs
        "failures": [f.to_dict() for f in failures],
        "note": "schedule+simulate stages only, shared pnr artifacts, cold "
                "stepper caches per repeat (includes jit compiles — the "
                "cost of a fresh simulate=True exploration); wall-clocks "
                "are medians over repeats",
    }
    _write(result, out_path)

    emit("explore_sim_serial", serial_s * 1e6,
         f"pairs={len(pairs)};compiles={len(pairs)}")
    emit("explore_sim_grouped", grouped_s * 1e6,
         f"pairs={len(pairs)};dispatches={disp['sim_dispatch']}")
    emit("explore_sim_speedup", grouped_s * 1e6,
         f"{speedup:.2f}x (target >=3x);repeats={repeats};out={out_path}")
    assert bit_identical and ii_identical and verified, \
        "batched schedule/simulate diverged from the per-pair path"
    if smoke:
        assert speedup > 1.0, (
            f"batched simulate slower than serial ({speedup:.2f}x)")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--simulate", action="store_true",
                    help="benchmark the schedule/simulate stages instead "
                         "of pnr (writes BENCH_sim_batch.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced budget + speedup>1 assertion (CI)")
    ap.add_argument("--repeats", type=int, default=None, metavar="N",
                    help="timed repeats per mode (default: 3 full, "
                         "1 smoke); artifacts record median + IQR")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="also write a Chrome trace of the benchmark run "
                         "(open in Perfetto / `python -m repro.obs.report`)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.trace:
        from repro import obs
        obs.enable_tracing()
    try:
        if args.simulate:
            run_sim(args.out or DEFAULT_SIM_OUT, smoke=args.smoke,
                    repeats=args.repeats)
        else:
            run(args.out or DEFAULT_OUT, smoke=args.smoke,
                repeats=args.repeats)
    finally:
        if args.trace:
            tracer = obs.disable_tracing()
            os.makedirs(os.path.dirname(args.trace) or ".", exist_ok=True)
            tracer.write_chrome(args.trace)
            print(f"# trace -> {args.trace}", file=sys.stderr)


if __name__ == "__main__":
    main()
