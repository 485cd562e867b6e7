"""``correct`` on the CPU at the cells' own configurations, short windows:
sound runs pass, the control (the reference in the precision below the
stated datapath, in the program's place) fails, and so does each fault a
cell can have, planted under the timed path.  A cell here has no exchange
between chips to leave out."""

import asyncio
import time

import pytest

import check
import faults
import harness

SEED = 2 ** 31 + 101
CELLS = ["ml16.seed_sweep", "ml16.area_only"]


def _windows(cell_name, plan, seconds=3.0, from_start=None):
    """Run one session; ``plan`` is a list of (tag, fault or None) windows,
    each fault planted for its window only.  ``from_start`` is planted
    before the service starts, so set-up runs with it too."""
    harness.import_program()
    cell = harness.load_cell(cell_name)
    out = {}

    async def go():
        s = harness.Session(cell, SEED, time.perf_counter())
        await s.start()
        try:
            await s.warm_up()
            for i, (tag, fault) in enumerate(plan):
                restore = faults.plant(fault) if fault else (lambda: None)
                try:
                    s.run_seed = SEED + i
                    run = await s.window(seconds)
                finally:
                    restore()
                args = (run.served, run.captures, cell.suite,
                        cell.config, s.run_seed)
                out[tag] = (check.numbers(*args),
                            check.numbers(*args, control=True))
        finally:
            await s.close()

    restore = faults.plant(from_start) if from_start else (lambda: None)
    try:
        asyncio.run(go())
    finally:
        restore()
    return cell, out


def _correct(cell, values):
    return check.verdict(values, cell.config["limits"])[0]


PLANTED = ["anneal_unchanged", "route_altered", "record_altered"]


@pytest.fixture(scope="module", params=CELLS)
def windows(request):
    return _windows(request.param, [("sound", None)]
                    + [(f, f) for f in PLANTED])


def test_sound_runs_are_correct_and_the_control_is_not(windows):
    cell, out = windows
    program, control = out["sound"]
    assert _correct(cell, program), sorted(program.items())
    assert not _correct(cell, control), control


@pytest.mark.parametrize("fault", PLANTED)
def test_each_fault_makes_the_run_incorrect(windows, fault):
    cell, out = windows
    assert not _correct(cell, out[fault][0]), out[fault][0]


@pytest.mark.parametrize("cell_name", CELLS)
def test_half_the_pairs_dropped_from_the_start_is_incorrect(cell_name):
    """Planted before set-up, so warm-up sees the same short responses:
    the stated pairs, not the program's own output, set what is due."""
    cell, out = _windows(cell_name, [("half_dropped", None)],
                         from_start="half_dropped")
    values = out["half_dropped"][0]
    assert values["requests_failed"] > 0
    assert not _correct(cell, values)


def test_an_altered_simulated_output_makes_the_run_incorrect():
    cell, out = _windows(CELLS[0], [("sim_altered", None)],
                         from_start="sim_altered")
    assert not _correct(cell, out["sim_altered"][0])
