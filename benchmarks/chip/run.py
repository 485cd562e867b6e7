#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload ml16.seed_sweep \\
        --seed 2147483660 --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root and the cell's files
beside this script (see ``harness.py``).  Needs an accelerator: on a CPU,
or with fewer chips than the cell asks for, it exits 1 and prints no
result.  Notes, and each number ``correct`` compares beside its limit,
go to standard error; the last line of standard output is the result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"run: cannot load workload {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    try:
        harness.import_program()
    except ImportError as e:
        print(f"run: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    harness.require_devices(cell.chips)
    cache = harness.use_compile_cache()
    print(f"# compile cache {cache}", file=sys.stderr)
    result, notes = harness.run_cell(cell, args.seed, args.seconds,
                                     bool(args.trace), T_PROCESS)
    for line in notes:
        print(f"# {line}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
