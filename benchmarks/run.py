"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the paper reference).  Run with ``PYTHONPATH=src python -m benchmarks.run``.

``--trace-dir DIR`` records every benchmark under its own tracer and
writes ``DIR/<name>.trace.json`` Chrome trace-event files (plus jax
compile events on a side track) — load them in Perfetto or summarize
with ``python -m repro.obs.report``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _benchmarks(repeats=None):
    from . import (explore_bench, fabric_camera_bench, fabric_ml_bench,
                   fig8_camera_specialization, fig10_image_pe_ip,
                   fig11_ml_pe, kernel_bench, mining_bench, pnr_bench,
                   sim_bench, table1_cgra_vs_asic)
    return [
        ("mining", mining_bench.run),          # pipeline throughput (Sec. IV)
        ("fig8_camera", fig8_camera_specialization.run),   # Fig. 8
        ("fig10_image_pe_ip", fig10_image_pe_ip.run),      # Fig. 10
        ("fig11_ml_pe", fig11_ml_pe.run),                  # Fig. 11
        ("table1", table1_cgra_vs_asic.run),               # Table I
        ("kernels", kernel_bench.run),  # TPU-adaptation kernel statistics
        # placer scaling (delta vs full), median of --repeats
        ("pnr", lambda: pnr_bench.run(repeats=repeats)),
        ("sim", sim_bench.run),         # time domain: achieved II + golden
        # batched vs serial pnr stage
        ("explore", lambda: explore_bench.run(smoke=True, repeats=repeats)),
        # Fig. 11 @ 16x16 -> records jsonl
        ("fabric_ml", lambda: fabric_ml_bench.run(fast=True)),
        # camera @ auto-fit 18x17 fabric
        ("fabric_camera", lambda: fabric_camera_bench.run(fast=True)),
    ]


def _run_traced(name, fn, trace_dir: str) -> None:
    """One fresh tracer per benchmark -> ``trace_dir/<name>.trace.json``."""
    from repro import obs
    obs.enable_tracing()
    obs.enable_telemetry()
    obs.jaxprof.enable()
    try:
        with obs.span(name):
            fn()
    finally:
        tracer = obs.disable_tracing()
        obs.enable_telemetry(False)
        obs.jaxprof.disable()
    path = os.path.join(trace_dir, f"{name}.trace.json")
    tracer.write_chrome(path)
    print(f"# trace -> {path}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write one Chrome trace per benchmark into DIR")
    ap.add_argument("--repeats", type=int, default=None, metavar="N",
                    help="timed repeats for the repeat-aware benches "
                         "(pnr/explore); their BENCH jsons record "
                         "median + IQR")
    args = ap.parse_args(argv)
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in _benchmarks(repeats=args.repeats):
        if args.trace_dir:
            _run_traced(name, fn, args.trace_dir)
        else:
            fn()
    print(f"# total benchmark wall time: {time.time()-t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
