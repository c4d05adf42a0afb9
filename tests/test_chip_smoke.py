"""``chip_smoke.py``'s phases, steered to SMOKE size on the CPU (kernels in
interpret mode), plus its guard against running without a chip."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def _cpu_env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra)


def test_phase_serve_smoke():
    line = chip_smoke.phase_serve(smoke=True, requests=8)
    assert line.startswith("checks passed:") and "compactions_verified" in line


def test_phase_train_smoke():
    out = chip_smoke.phase_train(smoke=True, batch=2, seq=64, steps=2)
    assert "f32_reference" in out


def test_phase_resume_smoke(tmp_path):
    out = chip_smoke.phase_resume(ckpt_dir=tmp_path / "ckpt")
    assert out == "resumed_steps=[6, 7]"


def test_phase_compare_one_vs_four_cpu_devices():
    # a fresh process: the CPU backend takes its device count at start-up
    code = ("import chip_smoke as c; "
            "print(c.phase_compare(smoke=True, batch=8, seq=32, steps=2))")
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1].startswith("step0=")


def test_no_chip_guard_exits_nonzero(tmp_path):
    r = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                       text=True, timeout=300, env=_cpu_env())
    assert r.returncode != 0 and '"ok"' not in r.stdout
    # alone in a directory, without the program, it fails as well
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    r = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=_cpu_env())
    assert r.returncode != 0 and '"ok"' not in r.stdout
