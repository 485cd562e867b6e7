"""The benchmark's machinery, driven by ``BENCHMARK.json`` and data files.

A cell names a configuration and a traffic mix.  Everything particular to
one of them sits in a file of its own, found by its name:

* ``BENCHMARK.json``'s ``configs[].file``: the exploration config as it is
  run, its app suite (``suites/*.json``), the mesh it states and the
  limits of each number ``correct`` compares;
* ``traffic/<traffic>.json``: clients, the per-request changes to the
  config, the warm-up rule and the response timeout, read by the one
  generator here (:func:`drive`);
* ``metrics/<metric>.py``: a ``read(window)`` that returns the metric's
  value, or None where the window holds nothing to read it from.

A run: start the program's ``ExploreService`` in this process, serve it
over NDJSON on TCP, warm up on the cell's own traffic (a seed stream
disjoint from the measured one) until a round of requests compiles
nothing, then drive the closed-loop clients for ``--seconds`` and keep
what the timed path produced (:class:`check.Captures`) to compare with
the reference once the window has closed.
"""

from __future__ import annotations

import asyncio
import copy
import gc
import importlib
import importlib.util
import json
import math
import os
import random
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import check
import tracereduce

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]

#: window compile events count what jax traces, lowers or compiles
COMPILE_EVENTS = "jax.compile.events"

#: a ``--trace 1`` run traces the last this-many seconds of its window,
#: from the first request sent after that point to the window's end.  The
#: chip's trace of this program holds about 250,000 op events a second,
#: stopping the profiler takes about 8 s per traced second, and a trace of
#: 51 s kept only about half of its device events.
TRACE_SLICE_S = 10.0


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]            # the configuration file
    traffic: Dict[str, Any]           # traffic/<traffic>.json
    suite: Dict[str, Any]             # app name -> Graph.to_dict blob
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Optional[Path] = None) -> Cell:
    bench = json.loads((bench_path or REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((REPO / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    suite = json.loads((HERE / config["suite"]).read_text())["apps"]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, suite=suite,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(metric: str) -> Callable[["Window"], Optional[float]]:
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile of all values, interpolated between ranks."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# what a metric reader is given
# ---------------------------------------------------------------------------
@dataclass
class Window:
    """The measured window, as the metric readers see it."""

    seconds: float
    setup_s: float
    latencies: List[float]            # every request of the window
    pairs: int                        # (PE, app) records served
    sim_pairs: int                    # of those, in simulating requests
    hist: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    spans: Optional[List[Tuple[str, float, float]]] = None   # traced only
    device: Optional[tracereduce.DeviceWindow] = None        # traced only

    def span_s(self, name: str) -> Optional[float]:
        if self.spans is None:
            return None
        return sum(t1 - t0 for n, t0, t1 in self.spans if n == name)

    def hist_mean(self, name: str) -> Optional[float]:
        count, total = self.hist.get(name, (0, 0.0))
        return total / count if count else None

    def module_s(self, prefix: str) -> Optional[float]:
        if self.device is None:
            return None
        hits = [t for n, t in self.device.module_s.items()
                if n.startswith(prefix)]
        return sum(hits) if hits else None


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def import_program() -> None:
    """Put the checkout's ``src`` on the path and import the program."""
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.serve  # noqa: F401  (raises ImportError without it)


def require_devices(chips: int) -> list:
    """The accelerator devices; exits 1 on a CPU or too few chips."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run: JAX found no device: {e}", file=sys.stderr)
        sys.exit(1)
    if devices[0].platform == "cpu" or len(devices) < chips:
        print(f"run: need {chips} accelerator chip(s), JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(1)
    return devices


def use_compile_cache() -> str:
    """The program's persistent compile cache (a set
    ``JAX_COMPILATION_CACHE_DIR`` as it is, else ``.jax_cache/`` in the
    checkout), with every compile kept however short."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def install_captures(captures: check.Captures) -> Callable[[], None]:
    """Keep references to what the annealer's dispatch, the scheduler and
    the batched stepper return; arguments and results pass unchanged.
    Returns the function that puts the program's own functions back."""
    pipeline = importlib.import_module("repro.explore.pipeline")
    sim = importlib.import_module("repro.sim")

    pnr_grouped = pipeline.pnr_grouped
    build_sim_batch = sim.build_sim_batch
    simulate_batch = sim.simulate_batch

    def pnr_kept(items, options, *a, **k):
        out = pnr_grouped(items, options, *a, **k)
        captures.add_pnr(options.seed, items, out)
        return out

    def build_kept(items, *a, **k):
        out = build_sim_batch(items, *a, **k)
        captures.link_programs(items, out)
        return out

    def simulate_kept(progs, inputs_list, *a, **k):
        out = simulate_batch(progs, inputs_list, *a, **k)
        captures.add_sim(progs, inputs_list, out)
        return out

    pipeline.pnr_grouped = pnr_kept
    sim.build_sim_batch = build_kept
    sim.simulate_batch = simulate_kept

    def restore() -> None:
        pipeline.pnr_grouped = pnr_grouped
        sim.build_sim_batch = build_sim_batch
        sim.simulate_batch = simulate_batch

    return restore


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _merge(base: Dict, changes: Dict) -> Dict:
    out = copy.deepcopy(base)
    for k, v in changes.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) else v
    return out


def seed_stream(run_seed: int, client: int, phase: str) -> Iterator[int]:
    """Fabric seeds of one client in one phase.  Warm-up seeds are odd
    and measured seeds even, so the two phases never share a request."""
    rng = random.Random(f"{run_seed}/{client}/{phase}")
    parity = 1 if phase == "warmup" else 0
    seen = set()
    while True:
        seed = 2 * rng.randrange(2 ** 29) + parity
        if seed not in seen:
            seen.add(seed)
            yield seed


def request_line(cell: Cell, rid: str, seed: int) -> bytes:
    config = _merge(cell.config["explore"], cell.traffic["request"])
    config["fabric"]["seed"] = seed
    line = {"id": rid, "op": "explore", "config": config,
            "apps": cell.suite}
    return (json.dumps(line) + "\n").encode()


async def drive(cell: Cell, port: int, streams: List[Iterator[int]],
                deadline: Optional[float] = None,
                rounds: Optional[int] = None,
                mark: Optional[Tuple[float, Callable[[], Any]]] = None
                ) -> List[check.Served]:
    """Closed loop: each client sends its next request once the last is
    answered, until ``deadline`` (perf_counter) or for ``rounds``.  With
    ``mark`` = (time, call), the first client about to send at or after
    that time makes the call first."""
    simulate = bool(_merge(cell.config["explore"], cell.traffic["request"])
                    ["fabric"]["simulate"])
    timeout = float(cell.traffic["response_timeout_s"])
    served: List[check.Served] = []
    pending_mark = [mark]

    async def client(c: int, seeds: Iterator[int]) -> None:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", port, limit=1 << 26)
        try:
            n = 0
            while (rounds is None or n < rounds) and (
                    deadline is None or time.perf_counter() < deadline):
                if pending_mark[0] and (time.perf_counter()
                                        >= pending_mark[0][0]):
                    call = pending_mark[0][1]
                    pending_mark[0] = None
                    call()
                seed = next(seeds)
                t0 = time.perf_counter()
                writer.write(request_line(cell, f"c{c}-{n}", seed))
                await writer.drain()
                try:
                    line = await asyncio.wait_for(reader.readline(),
                                                  timeout)
                except asyncio.TimeoutError:
                    line = b""
                resp = json.loads(line) if line else {
                    "ok": False, "error": f"no answer in {timeout}s"}
                served.append(check.Served(seed, simulate, resp,
                                           time.perf_counter() - t0, t0))
                n += 1
                if not line:              # that client can go no further
                    break
        finally:
            writer.close()
            await writer.wait_closed()

    await asyncio.gather(*(client(c, s) for c, s in enumerate(streams)))
    return served


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclass
class Run:
    """What a session hands back after its window."""

    window: Window
    traced: Optional[Window]          # the traced slice, in traced runs
    served: List[check.Served]
    captures: check.Captures
    window_compiles: int
    notes: List[str]


class Session:
    """The service, warmed on the cell's traffic, ready for windows."""

    def __init__(self, cell: Cell, run_seed: int, t_process: float):
        from repro.obs import jaxprof
        from repro.obs.metrics import MetricsRegistry
        from repro.serve import ExploreService

        self.cell, self.run_seed, self.t_process = cell, run_seed, t_process
        self.captures = check.Captures()
        self._restore = install_captures(self.captures)
        self.compiles = MetricsRegistry()
        jaxprof.enable(registry=self.compiles)
        self.service = ExploreService()
        self.notes: List[str] = []
        self.port = 0
        self._server = None

    async def start(self) -> None:
        await self.service.start()
        self._server = await self.service.serve_tcp("127.0.0.1", 0)
        self.port = self._server.sockets[0].getsockname()[1]

    async def close(self) -> None:
        try:
            if self._server is not None:
                self._server.close()
                await self._server.wait_closed()
            await self.service.aclose()
        finally:
            self._restore()

    def _streams(self, phase: str) -> List[Iterator[int]]:
        return [seed_stream(self.run_seed, c, phase)
                for c in range(int(self.cell.traffic["clients"]))]

    async def warm_up(self) -> None:
        """Rounds of the cell's own requests until ``quiet_rounds`` in a
        row compile nothing (at least ``min_rounds``, at most
        ``max_rounds``)."""
        rule = self.cell.traffic["warmup"]
        streams = self._streams("warmup")
        quiet = rounds = 0
        t0 = time.perf_counter()
        while rounds < rule["max_rounds"] and not (
                rounds >= rule["min_rounds"]
                and quiet >= rule["quiet_rounds"]):
            before = self.compiles.counter(COMPILE_EVENTS)
            await drive(self.cell, self.port, streams, rounds=1)
            rounds += 1
            quiet = quiet + 1 if self.compiles.counter(
                COMPILE_EVENTS) == before else 0
        cache = self.compiles.counters("jax.compilation_cache.")
        self.notes.append(
            f"warm-up {time.perf_counter() - t0}s: {rounds} rounds, "
            f"{self.compiles.counter(COMPILE_EVENTS)} compile events of "
            f"{self.compiles.histogram('jax.compile.secs').total}s, "
            f"persistent cache hits "
            f"{cache.get('jax.compilation_cache.cache_hits', 0)} misses "
            f"{cache.get('jax.compilation_cache.cache_misses', 0)}")
        self.captures.clear()

    async def window(self, seconds: float,
                     profile: Optional["Profile"] = None) -> Run:
        """One measured window; captures hold only its requests.  With a
        ``profile``, its last :data:`TRACE_SLICE_S` seconds are traced."""
        self.captures.clear()
        reg = self.service.metrics
        compiles0 = self.compiles.counter(COMPILE_EVENTS)
        opened: Dict[str, Any] = {}

        def open_trace() -> None:
            opened["hist"] = _hist(reg)
            profile.open()
            opened["t"] = profile.host_window[0]

        hist0 = _hist(reg)
        counts0 = reg.counters()
        pauses = GcPauses()
        t0 = time.perf_counter()
        mark = ((t0 + max(0.0, seconds - TRACE_SLICE_S), open_trace)
                if profile is not None else None)
        with pauses:
            served = await drive(self.cell, self.port,
                                 self._streams("window"),
                                 deadline=t0 + seconds, mark=mark)
        t1 = time.perf_counter()
        hist1 = _hist(reg)
        slowest = sorted(served, key=lambda s: -s.latency_s)[:5]
        counts = {k: v - counts0.get(k, 0)
                  for k, v in reg.counters().items()
                  if v != counts0.get(k, 0)}
        notes = self.notes + [
            pauses.note(),
            "slowest requests: " + ", ".join(
                f"{s.latency_s}s ({s.response.get('elapsed_ms')} ms in the "
                f"service; fabric seed {s.seed}, sent at {s.sent - t0}s)"
                for s in slowest),
            "program counters in the window: " + ", ".join(
                f"{k} {v}" for k, v in sorted(counts.items()))]
        traced = None
        if profile is not None:
            profile.close()
            traced = self._window(served, opened["t"], t1,
                                  opened["hist"], hist1)
        return Run(window=self._window(served, t0, t1, hist0, hist1),
                   traced=traced, served=served, captures=self.captures,
                   window_compiles=self.compiles.counter(COMPILE_EVENTS)
                   - compiles0, notes=notes)

    def _window(self, served: List[check.Served], t0: float, t1: float,
                hist0: Dict, hist1: Dict) -> Window:
        """The requests sent in [t0, t1], as the metric readers see them."""
        inside = [s for s in served if s.sent >= t0]
        return Window(
            seconds=t1 - t0, setup_s=t0 - self.t_process,
            latencies=[s.latency_s for s in inside],
            pairs=sum(len(s.response.get("records") or []) for s in inside),
            sim_pairs=sum(len(s.response.get("records") or [])
                          for s in inside if s.simulate),
            hist={k: (c - hist0.get(k, (0, 0.0))[0],
                      v - hist0.get(k, (0, 0.0))[1])
                  for k, (c, v) in hist1.items()})


class GcPauses:
    """The garbage collector's pauses while the context is open."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []     # (generation, s)
        self._t = 0.0

    def _callback(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def note(self) -> str:
        full = [t for g, t in self.pauses if g == 2]
        return (f"gc in the window: {len(self.pauses)} collections, "
                f"{sum(t for _, t in self.pauses)}s; {len(full)} full, "
                f"{sum(full)}s, longest {max(full, default=0.0)}s")


def _hist(reg) -> Dict[str, Tuple[int, float]]:
    return {k: (h["count"], h["sum"])
            for k, h in reg.to_dict()["histograms"].items()}


class Profile:
    """The device trace of one window, with the host spans beside it."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.tracer = None
        self.host_window: Tuple[float, float] = (0.0, 0.0)
        self._mark = None

    def open(self) -> None:
        import jax
        from repro import obs

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.tracer = obs.enable_tracing()
        self._origin = time.perf_counter() - self.tracer.now()
        self._mark = jax.profiler.TraceAnnotation(tracereduce.WINDOW_MARK)
        t = time.perf_counter()
        self._mark.__enter__()
        self.host_window = (t, t)

    def close(self) -> None:
        from repro import obs

        self._mark.__exit__(None, None, None)
        self.host_window = (self.host_window[0], time.perf_counter())
        obs.disable_tracing()

    def reduce(self, window: Window) -> Tuple[Dict[str, Any], List[str]]:
        """Stop the profiler, fill ``window.spans``/``device``; returns
        the breakdown and notes."""
        import jax
        from jax.profiler import ProfileData

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t0
        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            profile = ProfileData.from_file(str(files[-1]))
            mark = tracereduce.window_mark(profile)
            if mark is None:
                raise RuntimeError("the trace lacks the window annotation")
            lo, hi = mark
            offset = lo - self.host_window[0]
            skew = (hi - lo) - (self.host_window[1] - self.host_window[0])
            dev = tracereduce.reduce_devices(profile, lo, hi)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        spans = [(sp.name, self._origin + sp.t0 + offset,
                  self._origin + sp.t1 + offset)
                 for sp, _, _ in self.tracer.iter_spans()]
        window.spans = [(n, a, b) for n, a, b in spans]
        window.device = dev
        gaps = tracereduce.charge_gaps(dev.gaps,
                                       tracereduce.self_segments(spans))
        breakdown = {"device_ops": tracereduce.top(dev.op_self_s),
                     "idle_gaps": tracereduce.top(gaps)}
        notes = [f"profiler stopped in {t_stop}s; trace reduced in "
                 f"{time.perf_counter() - t0 - t_stop}s; window "
                 f"annotation {hi - lo}s against {self.host_window[1] - self.host_window[0]}s "
                 f"on the host clock (skew {skew}s)",
                 f"device busy {dev.busy_s}s of {dev.window_s}s over "
                 f"{dev.devices} device(s); programs "
                 + ", ".join(f"{k} {v}s" for k, v in
                             sorted(dev.module_s.items(),
                                    key=lambda kv: -kv[1])[:6])]
        return breakdown, notes


async def _session_window(cell: Cell, run_seed: int, seconds: float,
                          t_process: float, profile: Optional[Profile]
                          ) -> Tuple[Run, Dict[str, Any]]:
    import jax

    session = Session(cell, run_seed, t_process)
    await session.start()
    try:
        await session.warm_up()
        run = await session.window(seconds, profile=profile)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
    finally:
        await session.close()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    return run, device


def run_cell(cell: Cell, run_seed: int, seconds: float, trace: bool,
             t_process: float) -> Tuple[Dict[str, Any], List[str]]:
    """One run of one cell: (the result line's object, stderr notes)."""
    profile = Profile() if trace else None
    run, device = asyncio.run(_session_window(cell, run_seed, seconds,
                                              t_process, profile))
    w = run.window
    notes = list(run.notes)
    notes.append(f"set-up {w.setup_s}s")
    breakdown = None
    if profile is not None:
        breakdown, more = profile.reduce(run.traced)
        notes += more
        notes.append(f"traced the last {run.traced.seconds}s of the "
                     f"window: {len(run.traced.latencies)} requests, "
                     f"{run.traced.pairs} pairs")
        device["busy_s"] = run.traced.device.busy_s
        device["window_s"] = run.traced.device.window_s
    notes.append(f"window compiles: {run.window_compiles} compile events")
    notes.append(f"window {w.seconds}s, {len(run.served)} requests, "
                 f"{w.pairs} pairs")

    values = check.numbers(run.served, run.captures, cell.suite,
                           cell.config, run_seed)
    ok, compared = check.verdict(values, cell.config["limits"])
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(run.traced if trace else w)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": ok, "attempted": len(run.served),
              "failed": int(values["requests_failed"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    notes += [f"compared {k}: {v['value']} (limit {v['limit']})"
              for k, v in compared.items()]
    return result, notes
