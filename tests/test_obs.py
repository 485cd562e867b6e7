"""The observability subsystem (repro.obs): span trees, Chrome export,
metrics registry, anneal/scheduler telemetry, post-pnr analyzer — and the
load-bearing invariant that turning any of it on changes zero bits."""

import json

import numpy as np
import pytest

from repro import obs
from repro.explore import ExploreConfig, Explorer
from repro.fabric import FabricOptions, FabricSpec
from repro.graphir import trace_scalar
from repro.obs import trace as trace_mod
from repro.obs.metrics import (CounterView, Histogram, MetricsRegistry,
                               global_registry, reset_global_registry)
from repro.obs.report import aggregate_stages, load_trace_rows, stage_table


@pytest.fixture
def tracer():
    """A process-global tracer that is always torn down."""
    trace_mod.disable()
    t = trace_mod.enable()
    yield t
    trace_mod.disable()


def conv_app():
    def conv4(i0, i1, i2, i3, w0, w1, w2, w3, c):
        return (((i0 * w0) + (i1 * w1)) + (i2 * w2)) + (i3 * w3) + c
    return trace_scalar(conv4, ["i0", "i1", "i2", "i3",
                                "w0", "w1", "w2", "w3", "c"])


def small_cfg(**kw):
    from repro.core import MiningConfig
    fabric = FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                           sweeps=4, **{k: v for k, v in kw.items()
                                        if k in ("seed", "simulate")})
    return ExploreConfig(
        mode="per_app",
        mining=MiningConfig(min_support=2, max_pattern_nodes=5),
        max_merge=kw.get("max_merge", 2), fabric=fabric)


# ---------------------------------------------------------------------------
# span tree
# ---------------------------------------------------------------------------
def test_span_tree_nesting_and_paths(tracer):
    with obs.span("a", k=1):
        with obs.span("b"):
            pass
        with obs.span("c"):
            obs.event("m", x=2)
    walked = [(path, depth) for _, depth, path in tracer.iter_spans()]
    assert walked == [("a", 0), ("a/b", 1), ("a/c", 1), ("a/c/m", 2)]
    spans = {path: sp for sp, _, path in tracer.iter_spans()}
    assert spans["a"].attrs == {"k": 1}
    assert spans["a/c/m"].dur == 0.0                      # event: zero width
    assert spans["a"].t0 <= spans["a/b"].t0
    assert spans["a/b"].t1 <= spans["a/c"].t0 <= spans["a/c"].t1
    assert spans["a/c"].t1 <= spans["a"].t1
    assert tracer.span_names() == {"a", "b", "c", "m"}


def test_span_exception_safety(tracer):
    with pytest.raises(ValueError, match="boom"):       # never suppressed
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    # both spans closed despite the raise; the error is recorded
    spans = {path: sp for sp, _, path in tracer.iter_spans()}
    assert set(spans) == {"outer", "outer/inner"}
    assert spans["outer/inner"].error == "ValueError: boom"
    assert not tracer._stack
    # the tracer still works afterwards
    with obs.span("after"):
        pass
    assert "after" in tracer.span_names()


def test_disabled_tracing_is_free_and_inert():
    trace_mod.disable()
    # one shared no-op context manager: no allocation per call
    assert obs.span("x", a=1) is obs.span("y")
    assert obs.event("z") is None
    assert trace_mod.current() is None
    with obs.span("x"):
        pass                                   # still a working `with`


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------
def test_chrome_export_schema_and_containment(tracer, tmp_path):
    with obs.span("root", app="conv"):
        with obs.span("kid"):
            pass
    tracer.add_complete("backend_compile", 0.001, 0.005, track="jax-compile",
                        event="/jax/x")
    doc = tracer.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(meta) + len(xs) == len(events)
    # one thread_name per track: pipeline + jax-compile
    assert {m["args"]["name"] for m in meta} == {"pipeline", "jax-compile"}
    by_name = {e["name"]: e for e in xs}
    for e in xs:
        assert e["pid"] == 1 and e["cat"] == "repro"
        assert e["ts"] >= 0 and e["dur"] >= 0      # microseconds
    root, kid = by_name["root"], by_name["kid"]
    assert root["tid"] == kid["tid"] == 1
    assert by_name["backend_compile"]["tid"] == 2
    # nesting is encoded by time containment (rounded to 1ns in export)
    assert kid["ts"] >= root["ts"] - 1e-3
    assert kid["ts"] + kid["dur"] <= root["ts"] + root["dur"] + 2e-3
    assert root["args"] == {"app": "conv"}

    path = str(tmp_path / "t.trace.json")
    tracer.write_chrome(path)
    written = json.load(open(path))                # valid JSON round trip
    # the written file additionally embeds the run manifest
    assert written["traceEvents"] == doc["traceEvents"]
    assert written["displayTimeUnit"] == doc["displayTimeUnit"]
    man = written["metadata"]["manifest"]
    assert man["schema"] == 1 and man["xla_cache"] in ("off", "cold", "warm")


def test_jsonl_export_and_report_loaders(tracer, tmp_path):
    with obs.span("stage", pe="PE1"):
        with obs.span("work"):
            pass
    tracer.add_complete("compile", 0.0, 0.002, track="jax-compile")
    jl = str(tmp_path / "t.jsonl")
    ch = str(tmp_path / "t.trace.json")
    tracer.write_jsonl(jl)
    tracer.write_chrome(ch)

    rows_jl = load_trace_rows(jl)
    rows_ch = load_trace_rows(ch)
    assert [r["name"] for r in rows_jl] == ["stage", "work", "compile"]
    assert rows_jl[0]["path"] == "stage" and rows_jl[1]["path"] == "stage/work"
    assert rows_jl[2]["track"] == "jax-compile"
    # both formats aggregate to the same per-name counts
    agg_jl = {a["name"]: a["count"] for a in aggregate_stages(rows_jl)}
    agg_ch = {a["name"]: a["count"] for a in aggregate_stages(rows_ch)}
    assert agg_jl == agg_ch == {"stage": 1, "work": 1, "compile": 1}
    md = stage_table(rows_jl, markdown=True)
    assert md.startswith("| span |") and "| stage | 1 |" in md
    assert "work" in stage_table(rows_jl, limit=3)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_histogram_pow2_buckets():
    h = Histogram()
    for v in (0, 1, 3, 4, 5, 100):
        h.observe(v)
    assert h.count == 6 and h.total == 113
    assert (h.vmin, h.vmax) == (0, 100)
    assert h.buckets == {0: 1, 1: 1, 4: 2, 8: 1, 128: 1}
    assert h.mean == pytest.approx(113 / 6)


def test_counter_view_is_counter_compatible():
    reg = MetricsRegistry()
    view = reg.view()
    assert view["missing"] == 0                    # Counter-style default
    view["pnr_dispatch"] += 1
    view["pnr_dispatch"] += 2
    assert reg.counter("pnr_dispatch") == 3
    reg.inc("sched_group")
    assert dict(view) == {"pnr_dispatch": 3, "sched_group": 1}
    assert len(view) == 2 and "sched_group" in view
    # prefixed views window the same storage
    sub = reg.view("memo.hit.")
    sub["mine"] += 5
    assert reg.counter("memo.hit.mine") == 5
    assert dict(sub) == {"mine": 5}
    assert "memo.hit.mine" not in dict(sub)
    del sub["mine"]
    assert reg.counter("memo.hit.mine") == 0
    assert view.registry is reg


def test_registry_export_and_merge(tmp_path):
    a = MetricsRegistry()
    a.inc("c", 2)
    a.set_gauge("g", [1.0, 2.0])
    a.observe("h", 4)
    b = MetricsRegistry()
    b.inc("c", 3)
    b.observe("h", 9)
    a.merge_from(b)
    assert a.counter("c") == 5
    assert a.histogram("h").count == 2 and a.histogram("h").vmax == 9
    path = str(tmp_path / "m.json")
    a.write_json(path)
    doc = json.load(open(path))
    assert doc["counters"] == {"c": 5}
    assert doc["gauges"] == {"g": [1.0, 2.0]}
    assert doc["histograms"]["h"]["count"] == 2


def test_jaxprof_counts_compiles_into_registry():
    jax = pytest.importorskip("jax")
    reg = MetricsRegistry()
    assert obs.jaxprof.enable(registry=reg)
    try:
        # a fresh lambda forces a fresh trace+compile
        jax.jit(lambda x: x * 2 + 1)(np.float32(3))
    finally:
        obs.jaxprof.disable()
    assert reg.counter("jax.compile.events") > 0
    assert reg.histogram("jax.compile.secs").count > 0
    before = reg.counter("jax.compile.events")
    jax.jit(lambda x: x * 4 + 1)(np.float32(3))    # disabled: no ticks
    assert reg.counter("jax.compile.events") == before


def test_jaxprof_counts_compilation_cache_events():
    from jax import monitoring
    reg = MetricsRegistry()
    obs.jaxprof.enable(registry=reg)
    try:
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        # time the cache saved is not compile time
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 5.0)
    finally:
        obs.jaxprof.disable()
    assert reg.counter("jax.compilation_cache.cache_hits") == 1
    assert reg.counter("jax.compilation_cache.cache_misses") == 2
    assert reg.histogram("jax.compile.secs").count == 0
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert reg.counter("jax.compilation_cache.cache_hits") == 1


def test_compile_cache_env_wins_else_repo_default(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache
    from repro.obs.manifest import _xla_cache_state

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert DEFAULT_CACHE_DIR.name == ".jax_cache"
        assert (DEFAULT_CACHE_DIR.parent / "pyproject.toml").exists()
        # the manifest reads the directory JAX uses, not the variable
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert _xla_cache_state() == "cold"
        (tmp_path / "entry").write_text("x")
        assert _xla_cache_state() == "warm"
        jax.config.update("jax_compilation_cache_dir", None)
        assert _xla_cache_state() == "off"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# pipeline integration: memo accounting, shared stores
# ---------------------------------------------------------------------------
def test_memo_hit_miss_accounting_across_with_config():
    apps = {"conv": conv_app()}
    ex = Explorer(apps, small_cfg())
    ex.map()
    assert ex.metrics.counter("memo.miss.mine") == 1
    assert ex.metrics.counter("memo.hit.mine") == 0
    ex.map()                                  # warm: all hits, no misses
    assert ex.metrics.counter("memo.miss.mine") == 1
    assert ex.metrics.counter("memo.hit.mine") >= 1
    hits0 = ex.metrics.counter("memo.hit.mine")

    # a with_config clone shares BOTH the memo store and the registry, so
    # its upstream reuse shows up as hits (not fresh misses) in one place
    ex2 = ex.with_config(max_merge=1)
    assert ex2.metrics is ex.metrics
    assert ex2.stats.registry is ex.metrics
    ex2.map()
    assert ex.metrics.counter("memo.miss.mine") == 1
    assert ex.metrics.counter("memo.hit.mine") > hits0
    assert ex.metrics.counter("memo.miss.merge") == 2   # max_merge differs


# ---------------------------------------------------------------------------
# telemetry is bit-free: enabling it changes nothing
# ---------------------------------------------------------------------------
def test_anneal_telemetry_bit_identical_and_observed():
    from repro.fabric import anneal_jax_batch, lower, synthetic_netlist
    spec = FabricSpec(rows=4, cols=4)
    probs = [lower(synthetic_netlist(spec, fill=0.8, seed=s), spec)
             for s in (1, 3)]
    plain = anneal_jax_batch(probs, chains=2, seed=0, sweeps=8,
                             nonces=[11, 22], telemetry=False)
    reg = MetricsRegistry()
    tele = anneal_jax_batch(probs, chains=2, seed=0, sweeps=8,
                            nonces=[11, 22], telemetry=True, metrics=reg)
    for (s0, c0), (s1, c1) in zip(plain, tele):
        assert np.array_equal(s0, s1)              # placements: same bits
        assert np.array_equal(c0, c1)
    h = reg.histogram("pnr.anneal.accept_rate")
    assert h.count == len(probs)
    assert 0.0 < h.vmax <= 1.0
    curves = [k for k in reg.to_dict()["gauges"]
              if k.startswith("pnr.anneal.cost_curve.")]
    assert len(curves) == len(probs)
    from repro.fabric.place import CURVE_POINTS
    for k in curves:
        curve = reg.gauge(k)
        assert len(curve) == CURVE_POINTS
        # annealing improves: the curve ends no worse than it starts
        assert curve[-1] <= curve[0]


def test_scheduler_telemetry_counters():
    apps = {"conv": conv_app()}
    ex = Explorer(apps, small_cfg(simulate=True))
    pnrs = ex.pnr()
    from repro.sim import modulo_schedule
    reset_global_registry()
    pnr = next(iter(pnrs.values()))
    sched = modulo_schedule(pnr.netlist, pnr.placement, pnr.routes, pnr.spec)
    g = global_registry()
    # one attempt per II tried, >= 1 scan round, scans >= rounds
    assert g.counter("sched_attempts") >= 1
    assert g.counter("sched_rounds") >= 1
    assert g.counter("sched_scans") >= g.counter("sched_rounds")
    assert sched.ii >= sched.min_ii


def test_tracing_and_telemetry_bit_identical_explore_records():
    """The acceptance invariant: a fully-instrumented run (tracing +
    telemetry + compile hooks) produces byte-identical ExploreRecords."""
    apps = {"conv": conv_app()}
    cfg = small_cfg(simulate=True)
    plain = Explorer(apps, cfg).run().records()

    trace_mod.disable()
    obs.enable_tracing()
    obs.enable_telemetry()
    ex = Explorer(apps, cfg)
    obs.jaxprof.enable(registry=ex.metrics)
    try:
        traced = ex.run().records()
    finally:
        tracer = trace_mod.disable()
        obs.enable_telemetry(False)
        obs.jaxprof.disable()

    assert [r.to_dict() for r in traced] == [r.to_dict() for r in plain]
    # ... and the trace actually covered the pipeline
    names = tracer.span_names()
    for stage in ("mine", "rank", "merge", "map", "pnr", "schedule",
                  "simulate"):
        assert stage in names, f"missing {stage} span"
    assert ex.metrics.counter("pnr_dispatch") >= 1


@pytest.mark.parametrize("seed,max_merge", [(1, 1), (2, 2)])
def test_tracing_bit_identity_property(seed, max_merge):
    """Tracing on vs off is bit-identical across configs (cheap cases of
    the hypothesis property below; the exhaustive version is gated)."""
    apps = {"conv": conv_app()}
    cfg = small_cfg(seed=seed, max_merge=max_merge)
    plain = Explorer(apps, cfg).run().records()
    trace_mod.disable()
    obs.enable_tracing()
    try:
        traced = Explorer(apps, cfg).run().records()
    finally:
        trace_mod.disable()
    assert [r.to_dict() for r in traced] == [r.to_dict() for r in plain]


@pytest.mark.slow
@pytest.mark.parametrize("name,ii", [("camera", 17), ("laplacian", 11)])
def test_analyzer_names_skew_critical_nets_image_suite(name, ii):
    """The acceptance question the analyzer exists to answer: which nets
    pin camera at II=17 (laplacian at II=11) on the 8x8 fabric."""
    from repro.apps import image_graphs
    from repro.core import baseline_datapath, map_application
    from repro.core.dse import app_ops
    from repro.sim import build_sim

    app = image_graphs()[name]
    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, name)
    prog, pnr = build_sim(dp, mapping, app, FabricSpec(rows=8, cols=8),
                          place_backend="jax", chains=8, sweeps=16)
    report = obs.analyze_pnr(pnr, prog.schedule)
    assert report.ii == prog.ii == ii
    crit = report.skew_critical
    assert crit, f"{name}: II={ii} but no net individually requires it"
    assert report.to_dict()["skew_critical"] == [s.net for s in crit]
    # the named nets really do imply the achieved II
    assert max(s.implied_ii for s in crit) == ii
    assert "skew-critical" in report.render()


@pytest.mark.slow
def test_tracing_bit_identity_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    apps = {"conv": conv_app()}

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 7), max_merge=st.integers(1, 2),
           simulate=st.booleans())
    def prop(seed, max_merge, simulate):
        cfg = small_cfg(seed=seed, max_merge=max_merge, simulate=simulate)
        plain = Explorer(apps, cfg).run().records()
        trace_mod.disable()
        obs.enable_tracing()
        obs.enable_telemetry()
        try:
            traced = Explorer(apps, cfg).run().records()
        finally:
            trace_mod.disable()
            obs.enable_telemetry(False)
        assert [r.to_dict() for r in traced] == [r.to_dict() for r in plain]

    try:
        prop()
    finally:
        trace_mod.disable()
        obs.enable_telemetry(False)


# ---------------------------------------------------------------------------
# post-pnr analyzer
# ---------------------------------------------------------------------------
def test_analyzer_report_and_operand_skew():
    apps = {"conv": conv_app()}
    ex = Explorer(apps, small_cfg(simulate=True))
    pnrs = ex.pnr()
    pnr = next(iter(pnrs.values()))

    report = obs.analyze_pnr(pnr)                 # schedule-free report
    assert 0.0 < report.pe_util <= 1.0
    assert 0.0 < report.io_util <= 1.0
    assert report.overflow == 0
    assert sum(report.route_depth_hist.values()) == len(pnr.routes.nets)
    assert report.ii is None and report.skews == []
    assert report.skew_critical == []
    d = report.to_dict()
    assert "ii" not in d and d["overflow"] == 0

    from repro.sim import modulo_schedule
    sched = modulo_schedule(pnr.netlist, pnr.placement, pnr.routes, pnr.spec)
    full = obs.analyze_pnr(pnr, sched)
    assert full.ii == sched.ii and full.min_ii == sched.min_ii
    assert full.latch_depth == sched.latch_depth
    assert full.skews, "conv has dependence edges; skew table empty"
    for s in full.skews:
        assert s.wait >= 1                        # operand arrives first
        assert s.wait <= s.hold                   # schedule is legal
        assert 1 <= s.implied_ii <= sched.ii      # no edge beats the II
        assert s.slack == s.hold - s.wait
    assert full.mean_latch_util <= full.max_latch_util <= 1.0
    # skew-critical = the edges that pin the achieved II
    crit = full.skew_critical
    assert all(s.implied_ii >= full.ii for s in crit)
    text = full.render()
    assert "operand-skew table" in text and str(full.ii) in text
    dd = full.to_dict()
    assert dd["ii"] == sched.ii
    assert dd["skew_critical"] == [s.net for s in crit]
