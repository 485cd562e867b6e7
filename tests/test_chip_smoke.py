"""``chip_smoke.py`` on the CPU: it refuses to run, and its phases rehearse.

The script itself runs only on a TPU.  Here its refusal is checked, and
its placement and kernel phases run at small shapes with the Pallas
kernels interpreted, so a change that breaks a phase shows up before a
chip run.  The served phase is covered by the serve tests' byte-identity
checks.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    reason = proc.stderr.strip().splitlines()
    assert len(reason) == 1 and "no TPU" in reason[0]


@pytest.mark.parametrize("phase", ["placement", "hpwl", "alu", "pe_kernel"])
def test_phase_rehearses_on_cpu(smoke, phase, capsys):
    if phase == "placement":
        smoke.placement_phase(size=32, chains=2, sweeps=2)
    elif phase == "hpwl":
        # 32x32 has more nets than one hpwl_pallas block: the grid steps
        smoke.hpwl_checks(sizes=(32,), delta_size=16, swaps=4,
                          interpret=True)
    elif phase == "alu":
        smoke.alu_checks(rows=8, lanes=256, interpret=True)
    else:
        smoke.pe_kernel_checks(shape=(64, 256), interpret=True)
    lines = capsys.readouterr().out.splitlines()
    assert lines and all(line.startswith("# ") for line in lines)
