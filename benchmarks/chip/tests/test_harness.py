"""The harness on the CPU, without a chip: cells found by file name, a new
traffic file picked up with no code edit, percentiles over every request,
the result line's keys, and the one trace reduction."""

import json
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import harness
import tracereduce

HERE = Path(__file__).resolve().parent
BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cells_are_found_by_file_name(name):
    cell = harness.load_cell(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cell.config == json.loads((harness.REPO / cfg["file"]).read_text())
    assert cell.traffic == json.loads(
        (harness.HERE / "traffic" / f"{w['traffic']}.json").read_text())
    assert set(cell.suite) and all("nodes" in g for g in cell.suite.values())
    assert set(cell.config["limits"]) >= {"requests_failed", "hpwl_ratio"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("metric", METRICS)
def test_metrics_are_found_by_file_name(metric):
    read = harness.load_reader(metric)
    empty = harness.Window(seconds=1.0, setup_s=1.0, latencies=[], pairs=0,
                           sim_pairs=0)
    if metric != "setup_s":
        assert read(empty) is None        # nothing to read: no value


def test_a_new_traffic_file_is_picked_up_without_a_code_edit(tmp_path):
    name = f"_probe_{os.getpid()}"
    traffic = dict(json.loads(
        (harness.HERE / "traffic" / "seed_sweep.json").read_text()))
    traffic["request"] = {"fabric": {"simulate": True, "sim_iterations": 5}}
    traffic["clients"] = 3
    path = harness.HERE / "traffic" / f"{name}.json"
    bench = dict(BENCH)
    bench["workloads"] = BENCH["workloads"] + [
        {"name": f"ml16.{name}", "config": "ml16",
         "traffic": name,
         "chips": 1, "why": "probe"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    try:
        path.write_text(json.dumps(traffic))
        cell = harness.load_cell(f"ml16.{name}",
                                 bench_path=tmp_path / "BENCHMARK.json")
        line = json.loads(harness.request_line(cell, "r", 42))
    finally:
        path.unlink()
    assert cell.traffic == traffic
    assert line["config"]["fabric"]["sim_iterations"] == 5
    assert line["config"]["fabric"]["seed"] == 42
    assert line["apps"] == cell.suite
    streams = [harness.seed_stream(7, c, "window") for c in range(3)]
    firsts = {next(s) for s in streams}
    assert len(firsts) == 3 and all(s % 2 == 0 for s in firsts)


def test_seed_streams_repeat_and_keep_warm_up_apart():
    a = harness.seed_stream(2 ** 31 + 5, 0, "window")
    b = harness.seed_stream(2 ** 31 + 5, 0, "window")
    w = harness.seed_stream(2 ** 31 + 5, 0, "warmup")
    xs = [next(a) for _ in range(50)]
    assert xs == [next(b) for _ in range(50)]
    assert len(set(xs)) == 50
    assert not set(xs) & {next(w) for _ in range(50)}


def test_percentiles_are_taken_over_all_requests():
    rng = random.Random(3)
    lat = [rng.uniform(0.5, 1.0) for _ in range(137)]
    w = harness.Window(seconds=10.0, setup_s=1.0, latencies=lat, pairs=5,
                       sim_pairs=5)
    p50 = harness.load_reader("latency_p50_s")(w)
    p90 = harness.load_reader("latency_p90_s")(w)
    assert p50 == pytest.approx(np.percentile(lat, 50), abs=1e-12)
    assert p90 == pytest.approx(np.percentile(lat, 90), abs=1e-12)
    w.latencies = lat + [30.0] * 20      # a slow tail moves p90
    assert harness.load_reader("latency_p90_s")(w) == 30.0
    assert harness.load_reader("pairs_per_s")(w) == 0.5


@pytest.fixture(scope="module")
def cpu_result():
    """One real run of a cell on the CPU (the device check skipped)."""
    harness.import_program()
    cell = harness.load_cell("ml16.seed_sweep")
    return cell, harness.run_cell(cell, 2 ** 31 + 11, 2.0, False,
                                  time.perf_counter())


def test_the_last_line_holds_the_contract_keys(cpu_result):
    cell, (result, notes) = cpu_result
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert result["metrics"][m["name"]]["unit"] == m["unit"]
        assert result["metrics"][m["name"]]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["compared"]) == set(cell.config["limits"])
    assert any(n.startswith("window compiles: 0 ") for n in notes)
    assert notes[-1].startswith("compared ")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ml16.seed_sweep", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_an_accelerator():
    p = _run_py(harness.REPO)
    assert p.returncode != 0 and p.stdout == ""


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(harness.REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------------------
# the trace reduction
# ---------------------------------------------------------------------------
def test_busy_time_is_a_union_of_intervals():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7), (6.0, 7.0)]
    assert tracereduce.union(iv) == [(0.0, 3.0), (5.0, 7.0)]
    assert tracereduce.complement(tracereduce.union(iv), -1.0, 8.0) == [
        (-1.0, 0.0), (3.0, 5.0), (7.0, 8.0)]


def test_idle_gaps_are_charged_to_the_span_that_was_open():
    spans = [("serve.batch", 0.0, 10.0), ("pnr", 1.0, 6.0),
             ("pnr.dispatch", 2.0, 3.0), ("pnr.pair", 4.0, 5.0),
             ("schedule", 7.0, 9.0)]
    segs = tracereduce.self_segments(spans)
    assert segs == [(0.0, 1.0, "serve.batch"), (1.0, 2.0, "pnr"),
                    (2.0, 3.0, "pnr.dispatch"), (3.0, 4.0, "pnr"),
                    (4.0, 5.0, "pnr.pair"), (5.0, 6.0, "pnr"),
                    (6.0, 7.0, "serve.batch"), (7.0, 9.0, "schedule"),
                    (9.0, 10.0, "serve.batch")]
    got = tracereduce.charge_gaps([(-1.0, 0.5), (2.5, 4.5), (8.5, 12.0)],
                                  segs)
    assert got == pytest.approx({
        tracereduce.NO_SPAN: 1.0 + 2.0, "serve.batch": 0.5 + 1.0,
        "pnr.dispatch": 0.5, "pnr": 1.0, "pnr.pair": 0.5, "schedule": 0.5})


def test_op_self_time_leaves_out_nested_ops():
    evs = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0), ("fusion", 4.0, 6.0),
           ("copy", 11.0, 12.0)]
    assert tracereduce.self_times(evs) == pytest.approx(
        {"while": 6.0, "fusion": 4.0, "copy": 1.0})


@pytest.fixture(scope="module")
def recorded():
    """An excerpt of a TPU v5e trace of this program: one small exploration
    (the ``ds`` app, 2 chains, 1 sweep) inside the ``bench.window``
    annotation, with every program execution kept and the first 40 op
    events of the annealer's (``jit_chain``) and the stepper's
    (``jit_one``) first execution."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(HERE / "data" /
                                     "explore_excerpt.xplane.pb"))


def test_one_device_op_line_is_read_with_no_double_count(recorded):
    plane = tracereduce.device_planes(recorded)[0]
    lines = {l.name: list(l.events) for l in plane.lines}
    mods = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in lines["XLA Modules"]]
    lo, hi = tracereduce.window_mark(recorded)
    assert lo < min(a for a, _ in mods) and max(b for _, b in mods) < hi
    dev = tracereduce.reduce_devices(recorded, lo, hi)
    assert dev.busy_s == pytest.approx(
        sum(b - a for a, b in tracereduce.union(mods)))
    assert sum(dev.module_s.values()) == pytest.approx(
        sum(b - a for a, b in mods))
    assert {"jit_chain", "jit_one"} <= set(dev.module_s)
    # the op line nests (a while loop holds its body's ops): summed as
    # it stands it would count the body twice; self times do not
    ops = sorted((e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines["XLA Ops"])
    assert any(a0 <= a1 and b1 <= b0 and (a0, b0) != (a1, b1)
               for (a0, b0), (a1, b1) in zip(ops, ops[1:]))
    naive = sum(e.duration_ns for e in lines["XLA Ops"]) * 1e-9
    assert sum(dev.op_self_s.values()) < naive
    assert sum(dev.op_self_s.values()) <= dev.busy_s
    assert all(k.split("/")[0] in dev.module_s for k in dev.op_self_s)
    assert sum(b - a for a, b in dev.gaps) == pytest.approx(
        dev.window_s - dev.busy_s)
    assert 0 < dev.idle_pct < 100
    # a window that clips the first program counts only its inside
    a0, b0 = sorted(mods)[0]
    half = tracereduce.reduce_devices(recorded, (a0 + b0) / 2, hi)
    assert half.busy_s == pytest.approx(dev.busy_s - (b0 - a0) / 2)
