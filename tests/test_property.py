"""Hypothesis property tests on the system's core invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (Datapath, add_pattern, maximal_independent_set,
                        validate_config)
from repro.graphir import Graph
from repro.graphir.ops import OPS

# ops safe for random-pattern property testing (total functions)
_SAFE_OPS = ["add", "sub", "mul", "min", "max", "abs", "neg"]


@st.composite
def random_pattern(draw):
    """Connected random DAG of 2..5 safe ops (+ optional const leaf)."""
    n = draw(st.integers(2, 5))
    g = Graph()
    ids = []
    for i in range(n):
        op = draw(st.sampled_from(_SAFE_OPS))
        nid = g.add_node(op)
        # connect to a previous node on port 0 to stay connected
        if ids:
            src = draw(st.sampled_from(ids))
            arity = OPS[op].arity
            port = draw(st.integers(0, arity - 1)) if arity else 0
            g.add_edge(src, nid, port)
        ids.append(nid)
    if draw(st.booleans()):
        c = g.add_node("const", value=draw(st.floats(-2, 2, allow_nan=False)))
        # feed const into a free port if one exists
        from repro.graphir.graph import free_in_ports
        free = free_in_ports(g)
        free = [fp for fp in free if g.nodes[fp[0]] != "const"]
        if free:
            node, port = free[draw(st.integers(0, len(free) - 1))]
            g.add_edge(c, node, port)
    return g


@settings(max_examples=40, deadline=None)
@given(pats=st.lists(random_pattern(), min_size=1, max_size=3))
def test_merged_datapath_implements_every_pattern(pats):
    """THE merging invariant: after merging any sequence of patterns, every
    config still computes exactly its source subgraph through the muxes."""
    dp = Datapath()
    for i, p in enumerate(pats):
        add_pattern(dp, p, f"cfg{i}", validate=False)
    for name, cfg in dp.configs.items():
        ok, msg = validate_config(dp, cfg, trials=3)
        assert ok, f"{name}: {msg}"


@settings(max_examples=40, deadline=None)
@given(pats=st.lists(random_pattern(), min_size=2, max_size=3))
def test_merging_never_exceeds_disjoint_area(pats):
    merged = Datapath()
    total_disjoint = 0.0
    for i, p in enumerate(pats):
        add_pattern(merged, p, f"cfg{i}", validate=False)
        solo = Datapath()
        add_pattern(solo, p, "only", validate=False)
        total_disjoint += solo.area_um2()
    # merging may add muxes/config bits but must beat fully disjoint
    # datapaths on unit area; allow small bookkeeping slack
    assert merged.area_um2() <= total_disjoint * 1.05 + 50.0


@settings(max_examples=60, deadline=None)
@given(st.lists(st.frozensets(st.integers(0, 12), min_size=1, max_size=4),
                min_size=1, max_size=12))
def test_mis_independent_and_maximal(sets):
    picked = maximal_independent_set(sets)
    chosen = [sets[i] for i in picked]
    # independent
    for i in range(len(chosen)):
        for j in range(i + 1, len(chosen)):
            assert not (chosen[i] & chosen[j])
    # maximal: every unpicked set conflicts with some picked set
    picked_union = set()
    for s in chosen:
        picked_union |= s
    for i, s in enumerate(sets):
        if i not in picked:
            assert s & picked_union


@settings(max_examples=30, deadline=None)
@given(random_pattern())
def test_canonical_label_invariant_under_relabeling(g):
    assert g.canonical_label() == g.relabeled().canonical_label()


@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                min_size=1, max_size=300))
def test_int8_compression_error_bound(vals):
    """Quantization error <= half an LSB of the block scale."""
    import jax.numpy as jnp
    from repro.sharding.compression import _quantize, BLOCK
    x = jnp.asarray(np.asarray(vals, np.float32))
    q, scale = _quantize(x)
    deq = (q.astype(jnp.float32) * scale).reshape(-1)[:x.shape[0]]
    err = np.abs(np.asarray(deq) - np.asarray(x))
    n = x.shape[0]
    pad = (-n) % BLOCK
    scales = np.repeat(np.asarray(scale)[:, 0], BLOCK)[:n]
    assert np.all(err <= scales * 0.5 + 1e-6)


# ---------------------------------------------------------------------------
# placer: delta move scoring is bit-identical to full recompute
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(net_seed=st.integers(0, 10_000),
       fill=st.floats(0.25, 0.95),
       fanout=st.integers(1, 5),
       anneal_seed=st.integers(0, 1_000))
def test_property_delta_equals_full_recompute(net_seed, fill, fanout,
                                              anneal_seed):
    """Random netlists, random seeds: the delta-scored annealer accepts
    exactly the moves the full-recompute annealer accepts, so placements
    and costs come back bit-identical."""
    from repro.fabric import FabricSpec, synthetic_netlist
    from repro.fabric.place import anneal_jax_batch, lower

    spec = FabricSpec(rows=4, cols=4)
    nl = synthetic_netlist(spec, fill=fill, seed=net_seed,
                           max_fanout=fanout)
    p = lower(nl, spec)
    (s_d, c_d), = anneal_jax_batch([p], chains=2, seed=anneal_seed,
                                   sweeps=3, score_mode="delta")
    (s_f, c_f), = anneal_jax_batch([p], chains=2, seed=anneal_seed,
                                   sweeps=3, score_mode="full")
    assert np.array_equal(s_d, s_f)
    assert np.array_equal(c_d, c_f)


# ---------------------------------------------------------------------------
# time-domain subsystem: random graphs simulate bit-exactly
# ---------------------------------------------------------------------------
_SIM_OPS = ["add", "sub", "mul", "min", "max"]


@st.composite
def random_app_graph(draw):
    """Random small application DAG over IEEE-exact binary ops, with named
    inputs, optional integral consts, and 1-2 marked outputs."""
    n_in = draw(st.integers(2, 4))
    n_ops = draw(st.integers(3, 8))
    g = Graph()
    pool = [g.add_node("input", name=f"i{k}") for k in range(n_in)]
    for _ in range(draw(st.integers(0, 2))):
        pool.append(g.add_node("const",
                               value=float(draw(st.integers(-4, 4)))))
    input_used = False
    for _ in range(n_ops):
        op = draw(st.sampled_from(_SIM_OPS))
        nid = g.add_node(op)
        for port in range(2):
            if not input_used and port == 0:
                src = pool[0]                  # guarantee an array input
                input_used = True
            else:
                src = draw(st.sampled_from(pool))
            g.add_edge(src, nid, port)
        pool.append(nid)
    compute = [n for n, op in g.nodes.items()
               if op not in ("input", "const")]
    g.mark_output(compute[-1])
    extra = draw(st.sampled_from(compute))
    if extra != compute[-1]:
        g.mark_output(extra)
    return g


@settings(max_examples=12, deadline=None)
@given(random_app_graph())
def test_property_simulated_array_equals_interp(app):
    """Full time-domain flow on a random graph bit-matches the interpreter
    (map -> place -> route -> modulo-schedule -> cycle-accurate sim)."""
    from repro.core import baseline_datapath, map_application
    from repro.core.dse import app_ops
    from repro.fabric import FabricSpec
    from repro.sim import verify_mapping

    dp = baseline_datapath(app_ops(app))
    mapping = map_application(dp, app, "prop")
    assert not mapping.unmapped
    report = verify_mapping(dp, mapping, app, FabricSpec(4, 4),
                            iterations=2, batch=2, place_backend="python",
                            chains=1, sweeps=8)
    assert report.bit_exact and report.max_abs_err == 0.0, report.row()


# ---------------------------------------------------------------------------
# batch-first schedule/simulate: grouping never changes a bit
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(apps=st.lists(random_app_graph(), min_size=2, max_size=3),
       seed=st.integers(0, 1000))
def test_property_sim_batch_independent_of_grouping(apps, seed):
    """Random graphs, random seeds: batched modulo schedules equal the
    per-pair schedules exactly, and batched simulation returns the same
    bits whether a program runs alone, with its bucket-mates, or in any
    other bucket composition — the serial per-pair result is the
    grouping-independent reference both must hit."""
    from repro.core import baseline_datapath, map_application
    from repro.core.dse import app_ops
    from repro.fabric import FabricSpec, place_and_route
    from repro.sim import (build_sim, build_sim_batch, random_inputs,
                           sim_signature, simulate, simulate_batch)

    items, solo_progs = [], []
    for i, app in enumerate(apps):
        dp = baseline_datapath(app_ops(app))
        mapping = map_application(dp, app, f"prop{i}")
        assert not mapping.unmapped
        pnr = place_and_route(dp, mapping, app, FabricSpec(4, 4),
                              backend="python", chains=1, sweeps=4,
                              seed=seed)
        items.append((dp, mapping, app, pnr))
        solo_progs.append(build_sim(dp, mapping, app, pnr=pnr)[0])

    batch_progs = build_sim_batch(items)
    for s, b in zip(solo_progs, batch_progs):
        assert b.ii == s.ii and b.latency == s.latency
        assert b.schedule.start == s.schedule.start

    inputs = [random_inputs(p, 2, 2, seed=seed + i)
              for i, p in enumerate(solo_progs)]
    serial = [simulate(p, x) for p, x in zip(solo_progs, inputs)]
    # one grouping: singletons
    for i, p in enumerate(batch_progs):
        res = simulate_batch([p], [inputs[i]])[0]
        assert np.array_equal(res.outputs, serial[i].outputs)
    # another grouping: full buckets
    by_sig = {}
    for i, p in enumerate(batch_progs):
        by_sig.setdefault(sim_signature(p, 2, 2), []).append(i)
    for idxs in by_sig.values():
        batch = simulate_batch([batch_progs[i] for i in idxs],
                               [inputs[i] for i in idxs])
        for i, res in zip(idxs, batch):
            assert np.array_equal(res.outputs, serial[i].outputs)
