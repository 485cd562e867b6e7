#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/readings.py --workload ml16.seed_sweep \\
        --seeds 2147483701,2147483702,... --seconds 8 [--fault NAME]

One process sets the cell up once, then runs one short window at the
cell's own load per seed and prints, per seed, one JSON line with the
numbers ``correct`` compares for the program and for the control (the
reference in the precision below the stated datapath, in the program's
place).  ``--fault`` plants one of ``faults.PLANTS`` before set-up, so
the whole run has it.  The benchmark's own runs never run this.  Needs an
accelerator, like ``run.py``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


async def _readings(cell, seeds, seconds, fault):
    import check
    import faults
    import harness

    restore = faults.plant(fault) if fault else (lambda: None)
    session = harness.Session(cell, seeds[0], T_PROCESS)
    out = []
    try:
        await session.start()
        await session.warm_up()
        for seed in seeds:
            session.run_seed = seed
            run = await session.window(seconds)
            args = (run.served, run.captures, cell.suite,
                    cell.config, seed)
            out.append({"seed": seed, "requests": len(run.served),
                        "pairs": run.window.pairs,
                        "window_compiles": run.window_compiles,
                        "program": check.numbers(*args),
                        "control": check.numbers(*args, control=True)})
            print(json.dumps(out[-1]), flush=True)
    finally:
        await session.close()
        restore()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    cell = harness.load_cell(args.workload)
    harness.import_program()
    harness.require_devices(cell.chips)
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = asyncio.run(_readings(cell, seeds, args.seconds, args.fault))
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        ctrl = [r["control"][name] for r in rows]
        print(f"# {name}: program max {max(prog)}, control min "
              f"{min(ctrl)}, limit {cell.config['limits'][name]}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
