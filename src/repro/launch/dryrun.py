"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  * build the step (train/prefill/serve) with explicit in/out shardings,
  * ``jax.jit(...).lower(**abstract inputs).compile()``,
  * record memory_analysis(), cost_analysis() and collective bytes parsed
    from the optimized HLO (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute operand sizes),
  * derive the three roofline terms (DESIGN.md §7),
  * write one JSON artifact per cell under artifacts/dryrun/.

The dry-run is a host-only compile tool: it pins JAX to the CPU with 512
host devices before JAX initialises, and never takes an accelerator.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
      --shape train_4k [--multi-pod] [--all] [--out artifacts/dryrun]
"""

import argparse
import json
import os
import re
import sys
import time
import traceback

# v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 4.95e10             # bytes/s per link

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[-a-z0-9.]*\(", re.I)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)"
                       r"\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def _host_devices(n: int = 512) -> None:
    """Compile on ``n`` CPU host devices; must run before JAX initialises
    its backends (the production meshes need 256 and 512 devices)."""
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    import jax
    jax.config.update("jax_platforms", "cpu")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the HLO."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.-]+\s*=\s*(.*)$", line)
        if not m:
            continue
        rhs = m.group(1)
        cm = _COLL_RE.search(rhs)
        if cm is None:
            continue
        kind = cm.group(1).lower()
        # result shape(s) appear before the op name
        prefix = rhs[:cm.start()]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(prefix):
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * _DTYPE_BYTES.get(dt, 4)
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _compile_cell(cfg, shape: str, mesh, rules, train_overrides=None):
    """Lower + compile one step; return (compiled, cost, coll_bytes)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.shapes import SHAPES
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import (TrainConfig, build_decode_step,
                                  build_prefill_step, build_train_step)

    spec = SHAPES[shape]
    if spec.kind == "train":
        # 314B-class models need bf16 moments to fit (DESIGN.md §5)
        moment_dtype = (jnp.bfloat16 if cfg.param_count() > 5e10
                        else jnp.float32)
        tc = TrainConfig(adamw=AdamWConfig(moment_dtype=moment_dtype),
                         **(train_overrides or {}))
        fn, in_sh, out_sh, abstract = build_train_step(
            cfg, mesh, spec.global_batch, spec.seq, tc, rules)
        donate = (0, 1)
    elif spec.kind == "prefill":
        fn, in_sh, out_sh, abstract = build_prefill_step(
            cfg, mesh, spec.global_batch, spec.seq, rules)
        donate = ()
    else:
        fn, in_sh, out_sh, abstract = build_decode_step(
            cfg, mesh, spec.global_batch, spec.seq, rules)
        donate = (1,)
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        compiled = jitted.lower(*abstract).compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return compiled, cost, coll


def _scan_unit(cfg) -> int:
    """Layers per scan step (hybrid scans super-blocks)."""
    return cfg.attn_every if cfg.family == "hybrid" else 1


def corrected_costs(cfg, shape: str, mesh, rules, train_overrides=None):
    """Two-point loop correction for cost_analysis.

    XLA's cost analysis counts a while-loop body ONCE; with scanned layers
    the per-step flops/bytes/collectives are under-counted by the trip
    count.  We compile unrolled 1-unit and 2-unit variants (cheap):
        u1 = outside + body,  u2 = outside + 2·body
    and report  corrected = u1 + (steps − 1)·(u2 − u1).
    """
    import dataclasses as _dc
    unit = _scan_unit(cfg)
    steps = cfg.n_layers // unit
    c1 = _dc.replace(cfg, n_layers=unit, scan_layers=False)
    c2 = _dc.replace(cfg, n_layers=2 * unit, scan_layers=False)
    out = {}
    _, cost1, coll1 = _compile_cell(c1, shape, mesh, rules, train_overrides)
    _, cost2, coll2 = _compile_cell(c2, shape, mesh, rules, train_overrides)
    for key in ("flops", "bytes accessed"):
        u1 = float(cost1.get(key, 0.0))
        u2 = float(cost2.get(key, 0.0))
        out[key] = u1 + (steps - 1) * max(0.0, u2 - u1)
    coll = {}
    for kind in set(coll1) | set(coll2):
        u1 = coll1.get(kind, 0)
        u2 = coll2.get(kind, 0)
        coll[kind] = int(u1 + (steps - 1) * max(0, u2 - u1))
    return out, coll


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             rules_name: str = "default", extra_tag: str = "",
             train_overrides: dict = None, cfg_overrides: dict = None,
             rules_updates: dict = None) -> dict:
    _host_devices()
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, skip_reason
    from repro.parallel.sharding import default_rules, long_context_rules

    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_overrides)
    reason = skip_reason(cfg, shape)
    if reason is not None:
        return {"arch": arch, "shape": shape, "skipped": reason}
    spec = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    rules = (long_context_rules(mesh) if shape == "long_500k"
             else default_rules(mesh))
    if rules_updates:
        rules.update(rules_updates)
    t0 = time.time()
    # (1) full scanned module: proves sharding + compile, gives memory
    compiled, cost_raw, coll_raw = _compile_cell(cfg, shape, mesh, rules,
                                                 train_overrides)
    mem = compiled.memory_analysis()
    # (2) two-point loop correction for flops/bytes/collectives
    cost_fix, coll = corrected_costs(cfg, shape, mesh, rules,
                                     train_overrides)
    compile_s = time.time() - t0

    flops = cost_fix["flops"]
    hbm_bytes = cost_fix["bytes accessed"]
    coll_total = sum(coll.values())
    # cost_analysis is per-device post-SPMD; collective bytes parsed from
    # the (per-device) HLO likewise.
    t_compute = flops / PEAK_FLOPS
    t_memory = hbm_bytes / HBM_BW
    t_coll = coll_total / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    # decode processes 1 new token per sequence; train/prefill the full seq
    tokens = spec.global_batch * (1 if spec.kind == "decode" else spec.seq)
    n_param = cfg.param_count()
    n_active = cfg.active_param_count()
    if spec.kind == "train":
        model_flops = 6 * n_active * tokens
    else:
        model_flops = 2 * n_active * tokens
    model_flops_per_dev = model_flops / n_dev
    useful = model_flops_per_dev / flops if flops else 0.0

    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "rules": rules_name, "tag": extra_tag,
        "devices": n_dev,
        "kind": spec.kind,
        "compile_s": round(compile_s, 1),
        "params": n_param, "active_params": n_active,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0),
        },
        "cost": {"flops_per_dev": flops, "hbm_bytes_per_dev": hbm_bytes,
                 "raw_loop_flops": float(cost_raw.get("flops", 0.0)),
                 "raw_loop_bytes": float(cost_raw.get("bytes accessed",
                                                      0.0))},
        "collectives": coll,
        "collectives_raw_loop": coll_raw,
        "collective_bytes_per_dev": coll_total,
        "roofline": {**terms, "dominant": dominant,
                     "model_flops_per_dev": model_flops_per_dev,
                     "useful_flops_ratio": useful,
                     "step_time_bound_s": max(terms.values()),
                     "mfu_bound": (model_flops_per_dev / PEAK_FLOPS)
                     / max(max(terms.values()), 1e-12)},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape}_{result['mesh']}"
        if extra_tag:
            tag += f"_{extra_tag}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    _host_devices()
    from repro.configs import ARCHS
    from repro.launch.shapes import SHAPES, cells

    archs = args.arch or (list(ARCHS) if args.all else ["olmo-1b"])
    shapes = args.shape or list(SHAPES)
    runnable, skipped = cells(archs, shapes)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for a, s, reason in skipped:
        print(f"SKIP {a} {s}: {reason}", flush=True)
    failures = 0
    for a, s in runnable:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            try:
                r = run_cell(a, s, mp, args.out)
                ro = r["roofline"]
                print(f"OK {a} {s} {mesh_name} compile={r['compile_s']}s "
                      f"dom={ro['dominant']} "
                      f"t=({ro['compute_s']:.3e},{ro['memory_s']:.3e},"
                      f"{ro['collective_s']:.3e}) "
                      f"useful={ro['useful_flops_ratio']:.2f} "
                      f"mfu_bound={ro['mfu_bound']:.2f}", flush=True)
            except Exception as e:
                failures += 1
                print(f"FAIL {a} {s} {mesh_name}: {type(e).__name__}: {e}",
                      flush=True)
                traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
