#!/usr/bin/env python3
"""Run the served exploration end to end on one TPU chip, and check it.

    python3 chip_smoke.py

One process, through the entry points a user calls:

0. **device** — stop unless JAX's first device is a TPU: nothing here
   falls back to the CPU.  Report the device, the JAX version and the
   persistent compile-cache directory (:mod:`repro.compile_cache`).
1. **served exploration** — an in-process ``ExploreService`` answers
   NDJSON requests over TCP: the Fig. 11 ML suite per app on a 16x16
   fabric and the Fig. 10 image domain (PE_IP, camera included) on an
   auto-fit fabric, both simulated and golden-checked against
   ``graphir.interp``, sent at once; then the ML request again, which must
   come from the response cache.  Solo ``Explorer`` runs of both configs
   (``on_error="raise"``) must give the same record bytes.  No response may
   carry a StageFailure row, and no batch may have degraded or retried.
2. **mega-fabric placement** — the 128x128 synthetic netlist of the
   placer benchmark, placed twice by ``place_hierarchical`` with one seed:
   the placements must be identical and legal.
3. **Pallas kernels** — each opt-in kernel compiled for the chip
   (``interpret=False``) and compared with its jnp twin at a real shape;
   the jnp ALU is also compared with the NumPy interpreter's semantics op
   by op.

Every line but the last starts with ``# ``.  The last line is the JSON
result, printed only when every phase passed; a failed phase makes the
script exit 1 after the remaining phases ran.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: seconds one served response may take before the phase fails
RESPONSE_TIMEOUT_S = 900

#: ALU ops not held bit-equal to NumPy's semantics, only the Pallas kernel
#: to its jnp twin: the transcendentals and float pow, which no platform
#: rounds correctly, and div, recip and sqrt, which XLA on a TPU v5e does
#: not round as NumPy does (measured there).  No suite app uses them; the
#: pipeline's golden check fails any app that does and differs.
NOT_EXACT_OPS = ("div", "exp", "log", "pow", "recip", "rsqrt", "sigmoid",
                 "sqrt", "tanh")


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# phase 0: the device
# ---------------------------------------------------------------------------
def find_tpu():
    """The TPU device list; exits non-zero (printing no result) without one,
    or when the ``repro`` package is not next to this script."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        sys.exit(1)
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device is "
              f"{devices[0].platform!r}); refusing to run on the CPU",
              file=sys.stderr)
        sys.exit(1)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        sys.exit(2)
    cache_dir = use_compile_cache()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    return devices


def peak_device_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# phase 1: served exploration
# ---------------------------------------------------------------------------
def smoke_configs():
    """(ml per_app config, image domain config), FabricOptions defaults.

    Mining uses the paper-figure benchmarks' budget, inside which every
    suite app finishes on its own: the mined sets never depend on the
    clock, so solo and served runs compute the same records."""
    from repro.core.mining import MiningConfig
    from repro.explore import ExploreConfig
    from repro.fabric import FabricOptions, FabricSpec

    mining = MiningConfig(min_support=4, max_pattern_nodes=8,
                          time_budget_s=45, max_patterns_per_level=60)
    ml = ExploreConfig(
        mode="per_app", mining=mining, max_merge=3,
        fabric=FabricOptions(spec=FabricSpec(16, 16), simulate=True))
    # the smallest spec auto-grows to fit each pair (camera needs ~18x17)
    image = ExploreConfig(
        mode="domain", mining=mining, per_app_subgraphs=2,
        domain_name="PE_IP",
        fabric=FabricOptions(spec=FabricSpec(2, 2), simulate=True))
    return ml, image


async def _exchange(reader, writer, requests):
    """Send NDJSON requests at once on one connection; responses by id."""
    for rid, suite, cfg in requests:
        line = {"id": rid, "op": "explore", "suite": suite,
                "config": cfg.to_dict()}
        writer.write((json.dumps(line) + "\n").encode())
    await writer.drain()
    out = {}
    for _ in requests:
        line = await asyncio.wait_for(reader.readline(), RESPONSE_TIMEOUT_S)
        check(bool(line), "server closed the connection early")
        d = json.loads(line)
        out[d["id"]] = d
    return out


async def _serve(ml_cfg, image_cfg):
    from repro.serve import ExploreService

    async with ExploreService() as svc:
        server = await svc.serve_tcp("127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            try:
                t0 = time.perf_counter()
                resps = await _exchange(reader, writer,
                                        [("ml", "ml", ml_cfg),
                                         ("image", "image", image_cfg)])
                t1 = time.perf_counter()
                resps.update(await _exchange(reader, writer,
                                             [("ml-again", "ml", ml_cfg)]))
                t2 = time.perf_counter()
            finally:
                writer.close()
                await writer.wait_closed()
        finally:
            server.close()
            await server.wait_closed()
    return resps, svc.metrics, (t1 - t0, t2 - t1)


def served_phase(ml_cfg, image_cfg) -> None:
    from repro.apps import image_graphs, ml_graphs
    from repro.explore import Explorer

    resps, metrics, (t_first, t_cached) = asyncio.run(
        _serve(ml_cfg, image_cfg))
    for rid in ("ml", "image", "ml-again"):
        r = resps.get(rid)
        check(r is not None, f"no response for {rid}")
        check(r["ok"], f"{rid}: not ok: {r.get('error')}")
        check(not r["failures"], f"{rid}: StageFailure rows {r['failures']}")
        check(r["records"], f"{rid}: no records")
    check(not resps["ml"]["cached"] and not resps["image"]["cached"],
          "a first request came from the cache")
    check(resps["ml-again"]["cached"], "the repeat request missed the cache")
    check(resps["ml-again"]["records"] == resps["ml"]["records"],
          "the cached records differ from the first answer")
    degraded = metrics.counter("serve.batch_degraded")
    retries = metrics.counters("isolate.retry.")
    check(degraded == 0, f"serve.batch_degraded={degraded}")
    check(not any(retries.values()), f"retries {retries}")
    log(f"served: ml+image {t_first}s, cached repeat "
        f"{t_cached * 1e3}ms, pnr_dispatch="
        f"{metrics.counter('pnr_dispatch')} sim_dispatch="
        f"{metrics.counter('sim_dispatch')}")

    for rid, apps, cfg in (("ml", ml_graphs(), ml_cfg),
                           ("image", image_graphs(), image_cfg)):
        records = resps[rid]["records"]
        golden = sum(r["sim_verified"] == 1 for r in records)
        check(golden == len(records),
              f"{rid}: {len(records) - golden} records not golden-exact")
        t0 = time.perf_counter()
        ex = Explorer(apps, cfg.replace(on_error="raise"))
        res = ex.run()
        solo = [json.dumps(r.to_dict()) for r in res.records()]
        check([json.dumps(r) for r in records] == solo,
              f"{rid}: served records differ from the solo run")
        log(f"solo {rid}: {time.perf_counter() - t0}s, "
            f"{len(records)} records byte-identical to served, "
            f"{golden} golden bit-exact, pnr_dispatch="
            f"{ex.stats['pnr_dispatch']} "
            f"sim_dispatch={ex.stats['sim_dispatch']}")


# ---------------------------------------------------------------------------
# phase 2: mega-fabric placement
# ---------------------------------------------------------------------------
def placement_phase(size: int = 128, chains: int = 16, sweeps: int = 32,
                    seed: int = 5) -> None:
    from repro.fabric import FabricSpec, place_hierarchical, synthetic_netlist

    spec = FabricSpec(rows=size, cols=size)
    # the placer benchmark's locality-structured netlist (14,054 cells)
    nl = synthetic_netlist(spec, seed=4, locality=4)
    runs, walls = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        runs.append(place_hierarchical(nl, spec, chains=chains,
                                       sweeps=sweeps, seed=seed))
        walls.append(time.perf_counter() - t0)
    a, b = runs
    check(a.coords == b.coords and a.cost == b.cost
          and a.level_costs == b.level_costs,
          "two placements with one seed differ")
    check(set(a.coords) == set(nl.cells), "not every cell was placed")
    check(len(set(a.coords.values())) == len(a.coords),
          "two cells share a tile")
    check(all(spec.is_pe(a.coords[c.name]) for c in nl.pe_cells),
          "a PE cell is off the PE tiles")
    check(all(spec.is_io(a.coords[c.name]) for c in nl.io_cells),
          "an I/O cell is off the I/O ring")
    log(f"placement {size}x{size}: {len(nl.cells)} cells, grid "
        f"{a.cluster_grid}, hpwl={a.cost}, first {walls[0]}s, "
        f"second {walls[1]}s, identical and legal")


# ---------------------------------------------------------------------------
# phase 3: the opt-in Pallas kernels against their twins
# ---------------------------------------------------------------------------
def _random_positions(problem, rng):
    import numpy as np

    slot_of = np.concatenate([
        rng.permutation(problem.n_pe_slots),
        problem.n_pe_slots + rng.permutation(problem.n_io_slots)])
    return slot_of.astype(np.int32)


def _same(x, y) -> bool:
    import numpy as np

    return bool(np.array_equal(np.asarray(x), np.asarray(y),
                               equal_nan=True))


def hpwl_checks(sizes=(64, 128), delta_size: int = 64, swaps: int = 16,
                interpret: bool = False) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.fabric import FabricSpec, lower, synthetic_netlist
    from repro.kernels.pnr_cost import (hpwl, hpwl_delta, hpwl_delta_pallas,
                                        hpwl_pallas, net_hpwl)

    rng = np.random.default_rng(0)
    problems = {}
    for size in sorted(set(sizes) | {delta_size}):
        spec = FabricSpec(rows=size, cols=size)
        problems[size] = lower(synthetic_netlist(spec, seed=4), spec)
    for size in sizes:
        p = problems[size]
        pos = p.slot_xy[_random_positions(p, rng)]
        want = hpwl(pos, p.net_pins, p.net_mask)
        got = hpwl_pallas(pos, p.net_pins, p.net_mask, interpret=interpret)
        check(float(got) == float(want),
              f"hpwl_pallas {size}x{size}: {float(got)} != {float(want)}")
        log(f"hpwl_pallas == hpwl at {size}x{size} "
            f"({p.net_pins.shape[0]} nets): {float(want)}")

    p = problems[delta_size]
    n_nets, k = p.net_pins.shape[0], p.ent_nets.shape[1]
    slot_of = _random_positions(p, rng)
    pnc = net_hpwl(p.slot_xy[slot_of], p.net_pins, p.net_mask)
    args = [jnp.asarray(x) for x in (p.slot_xy, p.net_pins, p.net_mask)]
    for _ in range(swaps):
        a, b = (int(e) for e in rng.integers(0, p.n_entities, 2))
        cand = slot_of.copy()
        cand[a], cand[b] = cand[b], cand[a]
        nets = sorted({int(i) for i in np.concatenate(
            [p.ent_nets[a], p.ent_nets[b]]) if i < n_nets})
        touched = np.full(2 * k, n_nets, np.int32)
        touched[:len(nets)] = nets
        want = hpwl_delta(args[0], jnp.asarray(cand), args[1], args[2], pnc,
                          jnp.asarray(touched))
        got = hpwl_delta_pallas(args[0], jnp.asarray(slot_of), args[1],
                                args[2], pnc, jnp.asarray(touched),
                                jnp.int32(a), jnp.int32(b),
                                interpret=interpret)
        check(_same(got[0], want[0]) and _same(got[1], want[1]),
              f"hpwl_delta_pallas != hpwl_delta for swap ({a}, {b})")
    log(f"hpwl_delta_pallas == hpwl_delta at {delta_size}x{delta_size} "
        f"over {swaps} swaps")


def _max_ulps(x, y) -> int:
    """Largest distance in float32 units in the last place (NaN == NaN)."""
    import numpy as np

    def ordered(v):
        i = np.asarray(v, np.float32).ravel().view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    d = np.abs(ordered(x) - ordered(y))
    d[np.isnan(np.ravel(x)) & np.isnan(np.ravel(y))] = 0
    return int(d.max(initial=0))


def alu_checks(rows: int = 8, lanes: int = 4096,
               interpret: bool = False) -> None:
    import numpy as np

    from repro.kernels.sim_step import (ALU_IMPLS, alu_step_jnp,
                                        alu_step_pallas, alu_step_reference,
                                        op_table)

    ops = op_table(sorted(ALU_IMPLS))
    rng = np.random.default_rng(1)
    codes = (np.arange(lanes) % len(ops)).astype(np.int32)
    # golden-vector regime: integral operands, shift amounts like the apps'
    a = rng.integers(-256, 257, (rows, lanes)).astype(np.float32)
    b = rng.integers(0, 9, (rows, lanes)).astype(np.float32)
    c = rng.integers(-256, 257, (rows, lanes)).astype(np.float32)
    jnp_out = np.asarray(alu_step_jnp(codes, a, b, c, ops))
    pl_out = np.asarray(alu_step_pallas(codes, a, b, c, ops,
                                        interpret=interpret))
    with np.errstate(all="ignore"):          # 1/0, log(-1), ... on purpose
        ref_out = alu_step_reference(codes, a, b, c, ops)
    twin_diff, ref_diff = {}, {}
    for k, name in enumerate(ops):
        lanes_k = codes == k
        for diff, x in ((twin_diff, pl_out), (ref_diff, ref_out)):
            if not _same(x[:, lanes_k], jnp_out[:, lanes_k]):
                diff[name] = _max_ulps(x[:, lanes_k], jnp_out[:, lanes_k])

    def show(diff):
        return ", ".join(f"{op} ({u} ulp)" for op, u in diff.items()) \
            or "none"

    log(f"alu ({len(ops)} ops): pallas != jnp on {show(twin_diff)}; "
        f"jnp != numpy on {show(ref_diff)}; not held exact to numpy: "
        f"{', '.join(NOT_EXACT_OPS)}")
    check(not twin_diff, f"alu_step_pallas differs from alu_step_jnp on "
                         f"{show(twin_diff)}")
    inexact = [op for op in ref_diff if op not in NOT_EXACT_OPS]
    check(not inexact, f"alu_step_jnp is not exact on {inexact}")


def pe_kernel_checks(shape=(512, 512), interpret: bool = False) -> None:
    import numpy as np

    from repro.apps import ml_graphs
    from repro.explore import ExploreConfig, Explorer
    from repro.graphir.graph import free_in_ports
    from repro.graphir.interp import pattern_outputs
    from repro.kernels import fused_pe_apply

    ml_cfg, _ = smoke_configs()
    cfg = ExploreConfig(mode="per_app", mining=ml_cfg.mining,
                        max_merge=ml_cfg.max_merge)
    variant = Explorer({"conv": ml_graphs()["conv"]}, cfg).merge()["conv"][-1]
    rng = np.random.default_rng(2)
    for name in variant.merged_subgraphs:
        # mined constants are configuration registers: give each a value
        pattern = variant.datapath.configs[name].pattern.copy()
        for n, op in pattern.nodes.items():
            if op == "const":
                pattern.attrs.setdefault(n, {})["value"] = float(
                    rng.integers(1, 8))
        ports = free_in_ports(pattern)
        xs = [rng.integers(0, 256, shape).astype(np.float32) for _ in ports]
        got = fused_pe_apply(pattern, *xs, interpret=interpret)
        got = got if isinstance(got, tuple) else (got,)
        want = pattern_outputs(pattern, dict(zip(ports, xs)))
        check(len(got) == len(want) and all(
            _same(g, np.asarray(w, np.float32)) for g, w in zip(got, want)),
            f"fused_pe_apply({name}) differs from graphir.interp")
    log(f"fused_pe_apply == graphir.interp for {variant.name} of conv "
        f"({len(variant.merged_subgraphs)} merged configs, {shape})")


def kernel_phase(interpret: bool = False) -> None:
    hpwl_checks(interpret=interpret)
    alu_checks(interpret=interpret)
    pe_kernel_checks(interpret=interpret)


# ---------------------------------------------------------------------------
def main() -> int:
    t_start = time.perf_counter()
    devices = find_tpu()
    from repro.obs import jaxprof
    from repro.obs.metrics import MetricsRegistry

    compiles = MetricsRegistry()
    jaxprof.enable(registry=compiles)
    ml_cfg, image_cfg = smoke_configs()
    phases = [("served", lambda: served_phase(ml_cfg, image_cfg)),
              ("placement", placement_phase),
              ("kernels", kernel_phase)]

    def compile_totals():
        return (compiles.histogram("jax.compile.secs").total,
                compiles.counter("jax.compile.backend_compile_duration"))

    failed = []
    for name, run in phases:
        secs0, n0 = compile_totals()
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:
            traceback.print_exc()
            log(f"phase {name} FAILED: {type(e).__name__}: {e}")
            failed.append(name)
        secs, n = compile_totals()
        log(f"phase {name}: wall {time.perf_counter() - t0}s, compile "
            f"{secs - secs0}s over {n - n0} backend compiles, peak device "
            f"bytes {peak_device_bytes(devices[0])}")
    cache = compiles.counters("jax.compilation_cache.")
    log(f"total: wall {time.perf_counter() - t_start}s, compile "
        f"{compile_totals()[0]}s, persistent cache hits="
        f"{cache.get('jax.compilation_cache.cache_hits', 0)} misses="
        f"{cache.get('jax.compilation_cache.cache_misses', 0)}")
    if failed:
        log(f"FAILED phases: {', '.join(failed)}")
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
