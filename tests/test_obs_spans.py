"""Request-scoped tracing: span stacks per thread and asyncio task, the
service's request spans from the NDJSON front end to the annealer's
device wait, the anneal step counters, and the mirror of the program's
spans on the ``jax.profiler`` clock."""

import asyncio
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from repro import obs
from repro.core.mining import MiningConfig
from repro.explore import ExploreConfig
from repro.fabric import FabricOptions, FabricSpec
from repro.graphir import trace_scalar
from repro.obs import trace as trace_mod
from repro.obs.metrics import MetricsRegistry
from repro.serve import ExploreService, encode_request


@pytest.fixture
def tracer():
    trace_mod.disable()
    t = trace_mod.enable()
    yield t
    trace_mod.disable()


def _spans(tracer):
    return [sp for sp, _, _ in tracer.iter_spans()]


def _inside(kid, parent):
    return parent.t0 <= kid.t0 and kid.t1 <= parent.t1


def _problems(spec, seeds=(1, 3)):
    from repro.fabric import lower, synthetic_netlist
    return [lower(synthetic_netlist(spec, fill=0.8, seed=s), spec)
            for s in seeds]


# ---------------------------------------------------------------------------
# one stack per thread and per task
# ---------------------------------------------------------------------------
def test_threads_trace_apart(tracer):
    gate = threading.Barrier(2)

    def work(i):
        with obs.span(f"t{i}"):
            gate.wait()                   # both outer spans open at once
            with obs.span(f"t{i}.kid"):
                gate.wait()
                obs.event(f"t{i}.mark")
            gate.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    roots = {sp.name: sp for sp in tracer.roots}
    assert set(roots) == {"t0", "t1"}
    for i, th in enumerate(threads):
        root = roots[f"t{i}"]
        assert [c.name for c in root.children] == [f"t{i}.kid"]
        kid = root.children[0]
        assert [c.name for c in kid.children] == [f"t{i}.mark"]
        assert root.thread == kid.thread == th.ident
        assert _inside(kid, root)
    assert roots["t0"].thread != roots["t1"].thread
    assert tracer.open_spans() == ()
    paths = sorted(p for _, _, p in tracer.iter_spans())
    assert paths == ["t0", "t0/t0.kid", "t0/t0.kid/t0.mark",
                     "t1", "t1/t1.kid", "t1/t1.kid/t1.mark"]
    names = {tracer.thread_name(sp) for sp in tracer.roots}
    assert len(names) == 2
    tids = {e["tid"] for e in tracer.to_chrome()["traceEvents"]
            if e["ph"] == "X"}
    assert tids == {1, 2}                 # one track per thread


def test_asyncio_tasks_trace_apart(tracer):
    async def client(i, started, other):
        with obs.async_span(f"req{i}"):
            started.set()
            await other.wait()            # the other task's span is open
            with obs.span(f"req{i}.decode"):
                pass
            await asyncio.sleep(0)
            with obs.async_span(f"req{i}.encode"):
                await asyncio.sleep(0)

    async def go():
        a, b = asyncio.Event(), asyncio.Event()
        await asyncio.gather(client(0, a, b), client(1, b, a))

    asyncio.run(go())
    roots = {sp.name: sp for sp in tracer.roots}
    assert set(roots) == {"req0", "req1"}
    for i in range(2):
        assert [c.name for c in roots[f"req{i}"].children] == [
            f"req{i}.decode", f"req{i}.encode"]
    # the two requests overlap in time, yet neither holds the other's work
    assert roots["req0"].t0 < roots["req1"].t1
    assert roots["req1"].t0 < roots["req0"].t1
    assert tracer.open_spans() == ()


def test_a_disabled_tracers_stack_does_not_leak(tracer):
    ctx = obs.span("left.open")
    ctx.__enter__()                       # never closed in this tracer
    trace_mod.disable()
    fresh = trace_mod.enable()
    with obs.span("fresh"):
        assert obs.current_span().name == "fresh"
    assert [sp.name for sp in fresh.roots] == ["fresh"]
    assert fresh.open_spans() == ()


def test_record_span_goes_under_its_parent(tracer):
    with obs.async_span("req") as req:
        t0 = time.perf_counter()
        t1 = t0 + 0.002
    rec = obs.record_span("wait", t0, t1, req, rid="r")
    root = obs.record_span("orphan", t0, t1)
    assert req.children == [rec] and rec.attrs == {"rid": "r"}
    assert rec.dur == pytest.approx(0.002)
    assert root in tracer.roots
    trace_mod.disable()
    assert obs.record_span("x", 0.0, 1.0) is None
    assert obs.current_span() is None


def test_disabled_span_reads_no_clock(monkeypatch):
    trace_mod.disable()

    class NoClock:
        @staticmethod
        def perf_counter():
            raise AssertionError("clock read while tracing is off")

    monkeypatch.setattr(trace_mod, "time", NoClock)
    assert obs.span("x") is obs.async_span("y") is obs.span("z", a=1)
    with obs.span("x"), obs.async_span("y"):
        pass
    assert obs.record_span("q", 0.0, 1.0) is None


# ---------------------------------------------------------------------------
# a served request, front end to device wait
# ---------------------------------------------------------------------------
def _conv():
    def conv4(i0, i1, i2, i3, w0, w1, w2, w3, c):
        return (((i0 * w0) + (i1 * w1)) + (i2 * w2)) + (i3 * w3) + c
    return trace_scalar(conv4, ["i0", "i1", "i2", "i3",
                                "w0", "w1", "w2", "w3", "c"])


CFG = ExploreConfig(
    mode="per_app", mining=MiningConfig(min_support=2, max_pattern_nodes=5),
    max_merge=2,
    fabric=FabricOptions(spec=FabricSpec(rows=4, cols=4), chains=2,
                         sweeps=4))


def _serve_lines(lines):
    """Serve NDJSON lines through the service's connection loop."""
    out = []

    async def write_line(s):
        out.append(json.loads(s))

    async def go():
        async with ExploreService(max_wait_ms=5) as svc:
            reader = asyncio.StreamReader()
            for line in lines:
                reader.feed_data((json.dumps(line) + "\n").encode())
            reader.feed_eof()
            await svc._serve_stream(reader, write_line)
            return svc.metrics

    return out, asyncio.run(go())


def test_served_request_spans_join_by_rid(tracer):
    out, metrics = _serve_lines([encode_request("r1", {"conv": _conv()},
                                                CFG)])
    assert out[0]["ok"] and out[0]["records"]
    spans = _spans(tracer)
    names = {sp.name for sp in spans}
    assert not names & {"serve.request_done", "serve.request_failed",
                        "serve.cache_hit"}
    (req,) = [sp for sp in tracer.roots if sp.name == "serve.request"]
    assert req.attrs == {"rid": "r1", "ok": True, "cached": False,
                         "records": len(out[0]["records"]), "failures": 0}
    assert [c.name for c in req.children] == [
        "serve.decode", "serve.queue", "serve.encode"]
    decode, queue, encode = req.children
    assert queue.attrs == {"rid": "r1", "reason": "deadline"}
    assert decode.t1 <= queue.t0 and queue.t1 <= encode.t0
    for kid in req.children:
        assert _inside(kid, req) and kid.thread == req.thread
    assert metrics.histogram("serve.time_in_queue_ms").total == \
        pytest.approx(queue.dur * 1e3)
    # the batch ran on the executor thread, as a root of its own
    (batch,) = [sp for sp in tracer.roots if sp.name == "serve.batch"]
    assert batch.attrs["rids"] == ["r1"]
    assert batch.thread != req.thread
    assert queue.t1 <= batch.t0 and batch.t1 <= encode.t0
    by_path = {p: sp for sp, _, p in tracer.iter_spans()}
    pnr = next(sp for p, sp in by_path.items() if p.endswith("/pnr"))
    (lower,) = [c for c in pnr.children if c.name == "pnr.lower"]
    assert _inside(lower, pnr)
    dispatches = [sp for sp in spans if sp.name == "pnr.dispatch"]
    assert dispatches
    for d in dispatches:
        assert _inside(d, pnr)
        assert [c.name for c in d.children] == [
            "pnr.pack", "pnr.device", "pnr.unpack"]
        for kid in d.children:
            assert _inside(kid, d)
        assert {"problems", "chains", "s_pad", "steps_real", "steps_run",
                "cells", "nets", "pins"} <= set(d.attrs)
        assert 0 < d.attrs["steps_real"] <= d.attrs["steps_run"]
    steps_run = metrics.histogram("pnr.anneal.steps_run")
    assert steps_run.count == len(dispatches)
    assert steps_run.total == sum(d.attrs["steps_run"] for d in dispatches)


def test_failed_and_cached_requests_carry_their_outcome(tracer):
    line = encode_request("r2", {"conv": _conv()}, CFG)
    _serve_lines([{"id": "bad", "op": "nope"}])
    (bad,) = [sp for sp in tracer.roots if sp.name == "serve.request"]
    assert bad.attrs["rid"] == "bad" and bad.attrs["ok"] is False
    assert [c.name for c in bad.children] == ["serve.decode",
                                              "serve.encode"]

    async def twice():
        async with ExploreService(max_wait_ms=5) as svc:
            got = []

            async def write_line(s):
                got.append(json.loads(s))

            for _ in range(2):
                reader = asyncio.StreamReader()
                reader.feed_data((json.dumps(line) + "\n").encode())
                reader.feed_eof()
                await svc._serve_stream(reader, write_line)
            return got

    got = asyncio.run(twice())
    assert [g["cached"] for g in got] == [False, True]
    reqs = [sp for sp in tracer.roots if sp.name == "serve.request"
            and sp.attrs.get("rid") == "r2"]
    assert [r.attrs["cached"] for r in reqs] == [False, True]
    # a cache hit never queues
    assert [c.name for c in reqs[1].children] == ["serve.decode",
                                                  "serve.encode"]


# ---------------------------------------------------------------------------
# the annealer's work
# ---------------------------------------------------------------------------
def test_anneal_step_counters_match_a_hand_count():
    from repro.fabric import anneal_jax_batch, batch_signature
    spec = FabricSpec(rows=4, cols=4)
    p1, p2 = _problems(spec)
    sweeps, chains = 8, 3
    assert batch_signature(p1, sweeps) == batch_signature(p2, sweeps)
    cells = [p.n_pe_cells + p.n_io_cells for p in (p1, p2)]
    longest = sweeps * max(cells)
    s_pad = 1
    while s_pad < longest:
        s_pad *= 2
    reg = MetricsRegistry()
    anneal_jax_batch([p1, p2], chains=chains, seed=0, sweeps=sweeps,
                     nonces=[11, 22], metrics=reg)
    real = reg.histogram("pnr.anneal.steps_real")
    run = reg.histogram("pnr.anneal.steps_run")
    assert real.count == run.count == 1
    assert real.total == chains * sweeps * sum(cells)
    assert run.total == 2 * chains * s_pad
    assert batch_signature(p1, sweeps)[0] == s_pad
    anneal_jax_batch([p1], chains=chains, seed=0, sweeps=sweeps,
                     nonces=[11], metrics=reg)
    assert reg.histogram("pnr.anneal.steps_real").total == \
        chains * sweeps * (sum(cells) + cells[0])
    assert reg.histogram("pnr.anneal.steps_run").total == 3 * chains * s_pad


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------
def test_worker_spans_land_on_the_profilers_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    from repro.fabric import anneal_jax_batch

    spec = FabricSpec(rows=4, cols=4)
    probs = _problems(spec)
    anneal_jax_batch(probs, chains=2, sweeps=4)          # compile untraced

    def worker():
        with obs.span("pnr.dispatch"):
            anneal_jax_batch(probs, chains=2, sweeps=4)
        with obs.span("bench.outer"):
            time.sleep(0.003)
            with obs.span("bench.inner"):
                time.sleep(0.002)

    async def loop_side():
        with obs.async_span("loop.request"):
            await asyncio.sleep(0.001)

    trace_mod.disable()
    with jax.profiler.trace(str(tmp_path)):
        tracer = trace_mod.enable()
        try:
            with ThreadPoolExecutor(1) as pool:
                pool.submit(worker).result()
            asyncio.run(loop_side())
        finally:
            trace_mod.disable()
    files = sorted(Path(tmp_path).rglob("*.xplane.pb"))
    assert files
    profile = ProfileData.from_file(str(files[-1]))
    host = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(
                        ev.duration_ns * 1e-9)
    worker_spans = [sp for sp in _spans(tracer)
                    if sp.name != "loop.request"]
    assert {sp.name for sp in worker_spans} == {
        "pnr.dispatch", "pnr.pack", "pnr.device", "pnr.unpack",
        "bench.outer", "bench.inner"}
    for sp in worker_spans:
        assert sp.name in host, f"{sp.name} missing from the host plane"
        (dur,) = host[sp.name]
        assert abs(dur - sp.dur) < 1e-4, (sp.name, dur, sp.dur)
    assert "loop.request" not in host     # crosses an await: not mirrored
