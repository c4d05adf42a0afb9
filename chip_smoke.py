"""Chip smoke run: the serving and training entry points on a TPU.

Usage (from the repository root, on a machine with a TPU):
  python chip_smoke.py              # one chip: serve, train, resume
  python chip_smoke.py --chips 4    # four chips: cut olmo-1b on 1 vs 4
                                    # devices, then full olmo-1b on (4, 1)

Every phase runs in this one process and calls the drivers' ``main``
directly, so one process holds the chip.  Each phase prints one line with
its wall time, compile time (JAX's own compile events), persistent-cache
hits and the device's ``peak_bytes_in_use`` (a high-water mark since the
process started).  The last line of the output is one JSON object,
``{"ok": true, "device": {...}}``, printed only if every phase passed.
Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import shutil
import sys
import time
import traceback
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.train.data import synthetic_batch  # noqa: E402

BF16_RTOL = 2e-2        # agreement expected of a bf16 step with its reference
CKPT_DIR = ROOT / ".smoke_ckpt"
_STEP_RE = re.compile(r"step=(\d+) loss=(\S+) grad_norm=(\S+)")


class _Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def _call(main, argv):
    """Run a driver's ``main`` in this process; returns (rc, its stdout)."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    return rc, tee.buf.getvalue()


def _steps(out: str) -> dict:
    """{step: (loss, grad_norm)} from a training driver's output."""
    return {int(m[1]): (float(m[2]), float(m[3]))
            for m in _STEP_RE.finditer(out)}


def _close(a: float, b: float, rtol: float = BF16_RTOL) -> bool:
    return abs(a - b) <= rtol * max(abs(a), abs(b))


def _train_argv(arch, smoke, batch, seq, steps, layers=0):
    return (["--arch", arch, "--batch", str(batch), "--seq", str(seq),
             "--steps", str(steps)] + (["--smoke"] if smoke else [])
            + (["--layers", str(layers)] if layers else []))


def reference_loss(arch, smoke, batch, seq) -> float:
    """Step-0 loss of the training driver's params and batch, computed by a
    float32 forward at highest matmul precision."""
    cfg = get_config(arch, smoke=smoke)
    cfg32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    model = get_model(cfg)
    params = jax.jit(lambda key: model.init(cfg, key))(
        jax.random.PRNGKey(0))
    batch0 = synthetic_batch(cfg, 0, batch, seq)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, b: model.loss_fn(p, b, cfg32))(
            params, batch0)
    return float(loss)


# -- phases ----------------------------------------------------------------

def phase_serve(smoke=False, requests=32):
    """Full-width olmo-1b serving over a 2 GiB page pool (1024 pages of 16
    tokens, 16 layers), with the driver's own checks on."""
    rc, out = _call(serve.main, [
        "--arch", "olmo-1b", "--pages", "1024", "--page-size", "16",
        "--requests", str(requests), "--max-batch", "8",
        "--frag-threshold", "0.05", "--check"]
        + (["--smoke"] if smoke else []))
    assert rc == 0, "not every request completed"
    return out.strip().splitlines()[-1]


def phase_train(arch="mamba2-370m", smoke=False, batch=8, seq=1024,
                steps=5):
    rc, out = _call(train.main, _train_argv(arch, smoke, batch, seq, steps))
    assert rc == 0, f"train exited {rc}"
    got = _steps(out)
    assert sorted(got) == list(range(steps)), sorted(got)
    assert all(math.isfinite(loss) for loss, _ in got.values()), got
    want = reference_loss(arch, smoke, batch, seq)
    assert _close(got[0][0], want), (got[0][0], want)
    return f"step0_loss={got[0][0]} f32_reference={want}"


def phase_resume(ckpt_dir=CKPT_DIR):
    """Crash at step 5, resume from the checkpoint: the resumed steps
    replay the uninterrupted run's losses exactly."""
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    base = _train_argv("olmo-1b", True, 2, 32, 8)
    try:
        rc, out = _call(train.main, base)
        assert rc == 0
        whole = _steps(out)
        ck = base + ["--ckpt-dir", str(ckpt_dir), "--ckpt-every", "3"]
        rc, out = _call(train.main, ck + ["--fail-at", "5"])
        assert rc == 42 and "simulated failure" in out, rc
        crashed = _steps(out)
        rc, out = _call(train.main, ck + ["--resume"])
        assert rc == 0 and "resumed from step" in out, rc
        resumed = _steps(out)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    assert resumed and sorted(whole) == list(range(8))
    for run in (crashed, resumed):
        for step, (loss, _) in run.items():
            assert abs(loss - whole[step][0]) < 1e-6, (step, loss, whole)
    return f"resumed_steps={sorted(resumed)}"


def phase_compare(smoke=False, layers=2, batch=8, seq=1024, steps=3,
                  devices=4):
    """olmo-1b cut to ``layers`` layers: one device vs a (devices, 1) data
    mesh, same seed and batches."""
    argv = _train_argv("olmo-1b", smoke, batch, seq, steps, layers)
    runs = []
    for n in (1, devices):
        rc, out = _call(train.main, argv + ["--devices", str(n)])
        assert rc == 0, f"train on {n} device(s) exited {rc}"
        runs.append(_steps(out))
    one, many = runs
    assert sorted(one) == sorted(many) == list(range(steps))
    for step in one:
        for a, b in zip(one[step], many[step]):
            assert math.isfinite(a) and _close(a, b), (step, one, many)
    return " ".join(f"step{s}=({one[s][0]},{many[s][0]};{one[s][1]},"
                    f"{many[s][1]})" for s in sorted(one))


def phase_full4(steps=3):
    rc, out = _call(train.main, _train_argv("olmo-1b", False, 8, 1024, steps)
                    + ["--devices", "4"])
    assert rc == 0, f"train exited {rc}"
    got = _steps(out)
    assert sorted(got) == list(range(steps))
    assert all(math.isfinite(loss) for loss, _ in got.values()), got
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    return f"losses={[got[s][0] for s in sorted(got)]} per_chip_peak={peaks}"


# -- runner ----------------------------------------------------------------

class _CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling, and persistent
    cache hits, from JAX's own monitoring events."""

    def __init__(self):
        self.secs, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _run(name, fn, meter: _CompileMeter) -> bool:
    c0, h0, t0 = meter.secs, meter.hits, time.perf_counter()
    try:
        detail = fn()
        ok = True
    except Exception:
        traceback.print_exc()
        detail, ok = "FAILED", False
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase={name} ok={ok} wall_s={wall:.1f} "
          f"compile_s={meter.secs - c0:.1f} cache_hits={meter.hits - h0} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} {detail}",
          flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX runs on {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"{args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2
    use_compile_cache()
    meter = _CompileMeter()

    if args.chips == 4:
        phases = [("compare_1v4", phase_compare), ("olmo1b_4chip", phase_full4)]
    else:
        phases = [("serve", phase_serve), ("train", phase_train),
                  ("resume", phase_resume)]
    ok = all([_run(name, fn, meter) for name, fn in phases])
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
