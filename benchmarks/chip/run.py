"""Chip benchmark: run one cell of ``BENCHMARK.json`` once, on the chip.

Usage (from the repository root, on a machine with a TPU):
  python benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1> [--control]

Everything is found by name.  The cell in ``BENCHMARK.json`` names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the mix names the driver that runs this kind
of cell (``drivers/<driver>.py``).  Each per-layer metric is read by
``metrics/<metric>.py``.  A later cell, configuration, mix or metric is a
new file and a new entry, never an edit.

One process: build from the seed on the device, warm every shape the
window can reach, measure for ``--seconds``, read the device's peak
memory, free the program's state, then compare what the window produced
with the plain reference (``refs/``).  The last stdout line is one JSON
object; the numbers compared, each beside its limit, are the last lines
of stderr and the last key of that object.  ``--trace 1`` profiles the
window and reports the cell's per-layer metrics instead of its end-to-end
ones.  ``--control`` puts the reference, computed one precision lower, in
the program's place for the comparison (it must come out not correct).

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

T_PROCESS = time.perf_counter()

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
for p in (str(HERE), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CACHE_DIR = REPO / ".jax_cache"


def load_module(path: Path):
    """Import a file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: Path = REPO) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(manifest: Dict[str, Any], name: str) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = json.loads((REPO / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name) and m["moves"] in e2e_names]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


class CompileMeter:
    """Compile events and seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.count, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def use_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program:
    most serve programs compile in well under the default 1 s minimum."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> Dict[str, Any]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def peaks_for(kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and the hooks
    through which the harness times and traces the window."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    devices: list
    meter: CompileMeter
    smoke: bool = False            # SMOKE widths (CPU rehearsals, tests)
    t_window: Optional[float] = None
    setup_s: Optional[float] = None
    window_compiles: Optional[int] = None
    trace_dir: Optional[str] = None
    memory: Optional[Dict[str, Any]] = None

    def config(self) -> Dict[str, Any]:
        """The configuration file, at SMOKE widths where asked."""
        cfg = dict(self.cell.config)
        return dict(cfg, **cfg["smoke"]) if self.smoke else cfg

    def mix(self) -> Dict[str, Any]:
        """The traffic mix, with its SMOKE sizes where asked."""
        mix = dict(self.cell.traffic)
        return dict(mix, **mix.get("smoke", {})) if self.smoke else mix

    def start_window(self) -> None:
        """Called by the driver right before its first timed step."""
        import jax
        self.setup_s = time.perf_counter() - T_PROCESS
        self._compiles0 = self.meter.count + self.meter.hits
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # spans and device ops only
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t_window = time.perf_counter()

    def window_open(self) -> bool:
        return time.perf_counter() - self.t_window < self.seconds

    def end_window(self) -> float:
        """Called after the last timed step has been synced; returns the
        window's length.  Reads the device's peak memory before anything
        else runs."""
        import jax
        window_s = time.perf_counter() - self.t_window
        self._span.__exit__(None, None, None)
        self.window_compiles = (self.meter.count + self.meter.hits
                                - self._compiles0)
        if self.trace:
            jax.profiler.stop_trace()
        self.memory = device_info(self.devices)
        return window_s


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool = False, smoke: bool = False,
             require_tpu: bool = True,
             patch: Optional[Callable[[], None]] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result object.  ``require_tpu`` and
    ``smoke`` are for the rehearsals and the fault tests on the CPU, which
    never print a result under a device metric's name."""
    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
        if len(devices) < cell.chips:
            raise NoChip(f"{cell.chips} chips asked, {len(devices)} found")
        use_cache()
    devices = devices[:cell.chips]
    if patch is not None:
        patch()
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  control=control, devices=devices, meter=CompileMeter(),
                  smoke=smoke)
    driver = load_module(HERE / "drivers" / f"{cell.traffic['driver']}.py")
    out = driver.run(ctx)
    record = out["record"]
    e2e = dict(out["end_to_end"])
    e2e["peak_hbm_gib"] = ctx.memory["memory_peak_bytes"] / 2 ** 30
    e2e["setup_s"] = ctx.setup_s
    result: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"])}
    if trace:
        from reduce import reduce_trace
        if require_tpu:
            red = reduce_trace(ctx.trace_dir, n_devices=len(devices))
        else:   # a CPU rehearsal has no device plane to read
            red = {"window_s": record["window_s"], "busy_s": float("nan"),
                   "programs": {}, "top_programs": [], "idle_gaps": []}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        run = {"trace": red, "record": record, "cell": cell,
               "window_compiles": ctx.window_compiles,
               "peaks": peaks_for(devices[0].device_kind if require_tpu
                                  else "TPU v5 lite"), "chips": len(devices)}
        metrics = {}
        for m in cell.per_layer:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(ctx.memory, busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["top_programs"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = ctx.memory
    result["window_compiles"] = ctx.window_compiles
    result["setup"] = {k: v for k, v in record.items()
                       if isinstance(v, (int, float))}
    result["checks"] = out["checks"]
    return result


class NoChip(RuntimeError):
    pass


def format_checks(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {name}: {c['value']!r} limit {c['limit']!r}"
            for name, c in checks.items()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault of faults.py in the timed path "
                         "(must come out not correct)")
    ap.add_argument("--control", action="store_true",
                    help="compare the reference one precision lower in "
                         "the program's place (must fail)")
    args = ap.parse_args(argv)
    cell = find_cell(load_manifest(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control,
                          patch=(getattr(load_module(HERE / "faults.py"),
                                         args.fault) if args.fault else None))
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 2
    for line in format_checks(result["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(_finite(result)), flush=True)
    return 0


def _finite(x):
    """The result with every NaN or infinity as null (strict JSON)."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


if __name__ == "__main__":
    raise SystemExit(main())
