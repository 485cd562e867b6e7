"""``correct`` on the CPU for the image domain cell, ``image_ip.area_only``
(PE_IP on auto-fit arrays, simulation off), in short windows: a sound run
passes; the control (the reference in bfloat16, track-blind routes) fails,
and so does each fault that the cell can show."""

import pytest

from test_correct import _correct, _windows

CELL = "image_ip.area_only"
PLANTED = ["anneal_unchanged", "route_altered"]


@pytest.fixture(scope="module")
def windows():
    return _windows(CELL, [("sound", None)] + [(f, f) for f in PLANTED])


def test_a_sound_window_is_correct_and_the_control_is_not(windows):
    cell, out = windows
    program, control = out["sound"]
    assert _correct(cell, program), sorted(program.items())
    assert program["route_violations"] == 0
    assert not _correct(cell, control), control


@pytest.mark.parametrize("fault", PLANTED)
def test_each_fault_makes_the_window_incorrect(windows, fault):
    cell, out = windows
    assert not _correct(cell, out[fault][0]), out[fault][0]


def test_half_the_pairs_dropped_from_the_start_is_incorrect():
    cell, out = _windows(CELL, [("half_dropped", None)],
                         from_start="half_dropped")
    values = out["half_dropped"][0]
    assert values["requests_failed"] > 0
    assert not _correct(cell, values)
