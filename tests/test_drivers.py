"""Driver-level integration: crash/resume training determinism, space-cap
stall behaviour, serve driver completion."""

import os
import re
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ENV = dict(os.environ,
           PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _losses(out: str):
    return {int(m.group(1)): float(m.group(2))
            for m in re.finditer(r"step=(\d+) loss=([0-9.]+)", out)}


@pytest.mark.slow
def test_train_crash_resume_replays_identically(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "olmo-1b",
            "--smoke", "--steps", "8", "--batch", "2", "--seq", "32",
            "--ckpt-dir", ckpt, "--ckpt-every", "3"]
    r1 = subprocess.run(base + ["--fail-at", "5"], env=ENV,
                        capture_output=True, text=True, timeout=600)
    assert "simulated failure" in r1.stdout, r1.stdout + r1.stderr
    first = _losses(r1.stdout)
    r2 = subprocess.run(base + ["--resume"], env=ENV, capture_output=True,
                        text=True, timeout=600)
    assert "training done" in r2.stdout, r2.stdout + r2.stderr
    assert "resumed from step" in r2.stdout, r2.stdout
    second = _losses(r2.stdout)
    # resumed steps replay the uninterrupted trajectory exactly
    for step, loss in second.items():
        if step in first:
            assert abs(loss - first[step]) < 1e-6, (step, loss, first[step])


def test_space_cap_stalls_and_gc_frees():
    from repro.bench import WorkloadSpec, gen_load, gen_update, make_db, \
        run_phase
    spec = WorkloadSpec(value_kind="fixed-8192", dataset_bytes=4 << 20,
                        update_bytes=12 << 20)
    db = make_db("scavenger_plus", spec, space_limit_x=1.5)
    run_phase(db, "load", gen_load(spec), drain=True)
    run_phase(db, "update", gen_update(spec), drain=True)
    cap = db.opts.space_cap_bytes
    # the cap held (small transient breach tolerance for in-flight writes)
    assert db.device.total_bytes() <= 1.25 * cap
    assert db.stats_counters["gc_runs"] > 0


def test_serve_driver_main():
    from repro.launch.serve import main
    assert main(["--smoke", "--requests", "6", "--pages", "64",
                 "--max-batch", "2"]) == 0
