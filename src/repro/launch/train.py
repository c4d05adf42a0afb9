"""Fault-tolerant training driver.

Features exercised at laptop scale (same code path scales to the
production mesh — the dry-run compiles the identical step):

* checkpoint/restart on the LSM-backed store (``--resume`` continues from
  the latest durable step; crash-consistent via WAL + manifest);
* straggler detection: per-step wall-time EWMA; steps slower than
  ``straggler_factor``× the EWMA are logged (on a real fleet this signal
  feeds the controller that re-shards or restarts the slow host);
* elastic resume: checkpoints store full (unsharded) tensors — a restart
  on a different mesh re-shards on load (``restore(like=...)``).

Parameters and optimizer state are created by one jitted program with the
step's own shardings, so on a data mesh each device holds only its share
from the start.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir ckpt [--resume]
      [--fail-at 7] [--layers 2] [--devices 1]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from ..checkpoint import CheckpointConfig, CheckpointStore
from ..configs import get_config
from ..models import get_model
from ..train.data import synthetic_batch
from ..train.optimizer import AdamWConfig, init_state
from ..train.step import TrainConfig, build_train_step, record_routing
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="simulate a crash after this step")
    ap.add_argument("--straggler-factor", type=float, default=2.0)
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (0 = all)")
    ap.add_argument("--devices", type=int, default=0,
                    help="data-parallel mesh over this many devices "
                         "(0 = all local devices)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    mesh = make_host_mesh(devices=args.devices)
    tc = TrainConfig(adamw=AdamWConfig(lr=1e-3))
    fn, in_sh, out_sh, abstract = build_train_step(
        cfg, mesh, args.batch, args.seq, tc)
    jit_step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1))

    model = get_model(cfg)

    def init(key):
        params = model.init(cfg, key)
        return params, init_state(params, tc.adamw)

    params, opt = jax.jit(init, out_shardings=in_sh[:2])(
        jax.random.PRNGKey(0))
    start_step = 0

    store = None
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir, CheckpointConfig(keep_last=2),
                                recover=args.resume)
        if args.resume:
            step, state = store.restore(like={"params": params, "opt": opt})
            if step is not None:
                params, opt = state["params"], state["opt"]
                start_step = step + 1
                print(f"resumed from step {step}", flush=True)

    ewma = None
    for step in range(start_step, args.steps):
        batch = {k: jax.numpy.asarray(v) for k, v in
                 synthetic_batch(cfg, step, args.batch, args.seq).items()}
        t0 = time.perf_counter()
        params, opt, metrics = jit_step(params, opt, batch)
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        record_routing(metrics)
        ewma = dt if ewma is None else 0.8 * ewma + 0.2 * dt
        straggler = dt > args.straggler_factor * ewma and step > start_step
        print(f"step={step} loss={loss:.4f} "
              f"grad_norm={float(metrics['grad_norm']):.6g} "
              f"dt={dt * 1e3:.0f}ms"
              + (" STRAGGLER" if straggler else ""), flush=True)
        if store and (step + 1) % args.ckpt_every == 0:
            store.save(step, {"params": params, "opt": opt},
                       extra={"loss": loss})
        if args.fail_at is not None and step == args.fail_at:
            print("simulated failure — exiting uncleanly", flush=True)
            return 42
    if store:
        store.save(args.steps - 1, {"params": params, "opt": opt})
    print("training done", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
