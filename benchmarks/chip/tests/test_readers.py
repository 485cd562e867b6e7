"""The readers of the request, lowering, packing and anneal-work metrics on
a synthetic window: what each divides by, and None where the window holds
nothing to read (an untraced run, or a program without the span or
counter)."""

import pytest

import harness
import tracereduce


def _window(**kw):
    base = dict(seconds=10.0, setup_s=1.0, latencies=[0.5] * 4, pairs=56,
                sim_pairs=56)
    base.update(kw)
    return harness.Window(**base)


def _traced(**kw):
    spans = [("serve.request", 0.0, 0.6), ("serve.decode", 0.0, 0.002),
             ("serve.queue", 0.002, 0.052), ("serve.encode", 0.59, 0.591),
             ("serve.request", 1.0, 1.6), ("serve.decode", 1.0, 1.004),
             ("serve.encode", 1.59, 1.593),
             ("pnr", 0.06, 0.5), ("pnr.lower", 0.06, 0.088),
             ("pnr.dispatch", 0.09, 0.2), ("pnr.pack", 0.09, 0.1),
             ("pnr.device", 0.1, 0.19), ("pnr.unpack", 0.19, 0.2),
             ("pnr.dispatch", 0.2, 0.3), ("pnr.pack", 0.2, 0.206)]
    dev = tracereduce.DeviceWindow(
        window_s=10.0, busy_s=6.0, devices=1,
        module_s={"jit_chain": 1.2, "jit_one": 0.2})
    hist = {"pnr.anneal.steps_real": (6, 3.0e6),
            "pnr.anneal.steps_run": (6, 4.0e6),
            "serve.time_in_queue_ms": (2, 100.0)}
    return _window(spans=spans, device=dev, hist=hist, **kw)


def read(name, w):
    return harness.load_reader(name)(w)


def test_front_end_time_per_request():
    # (2 + 1 + 4 + 3) ms of decode and encode over 4 requests
    assert read("frontend.ms_per_request", _traced()) == pytest.approx(2.5)
    # the requests are the slice's, whatever the spans' count
    w = _traced(latencies=[0.5] * 2)
    assert read("frontend.ms_per_request", w) == pytest.approx(5.0)


def test_lowering_and_packing_per_pair():
    w = _traced(pairs=14)
    assert read("pnr.lower_ms_per_pair", w) == pytest.approx(28.0 / 14)
    assert read("pnr.pack_ms_per_pair", w) == pytest.approx(16.0 / 14)


def test_anneal_work_from_the_step_counters():
    w = _traced()
    assert read("anneal.useful_step_pct", w) == pytest.approx(75.0)
    assert read("anneal.device_ns_per_step", w) == pytest.approx(
        1e9 * 1.2 / 4.0e6)


NEW = ["frontend.ms_per_request", "pnr.lower_ms_per_pair",
       "pnr.pack_ms_per_pair", "anneal.useful_step_pct",
       "anneal.device_ns_per_step"]


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    assert read(name, _window()) is None              # untraced, no counters
    # traced, from a program with none of the new spans or counters
    old = _traced()
    old.spans = [s for s in old.spans
                 if s[0] not in {"serve.decode", "serve.encode",
                                 "pnr.lower", "pnr.pack"}]
    old.hist = {"serve.time_in_queue_ms": (2, 100.0)}
    assert read(name, old) is None


def test_the_step_share_reads_only_the_counters():
    untraced = _window(hist={"pnr.anneal.steps_real": (1, 30.0),
                             "pnr.anneal.steps_run": (1, 40.0)})
    assert read("anneal.useful_step_pct", untraced) == pytest.approx(75.0)
    assert read("anneal.device_ns_per_step", untraced) is None
