"""Tests of the chip benchmark that need no chip.

Run from the repository root on the CPU:
  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

* the trace reduction on hand-made intervals and on a small trace in a
  TPU trace's layout (``testdata/two_steps.xplane.txt``, an XSpace text
  proto);
* the operation and byte counts of ``work.py`` against hand-worked shapes;
* the traffic generator: every seed offers the same lengths;
* ``run.py`` exits non-zero, printing no result, without a TPU;
* both drivers rehearsed end to end at SMOKE widths, with the window's
  shapes warmed by the replay (no compile inside the window);
* the control (the reference one precision lower in the program's place)
  and every fault of ``faults.py`` come out not correct.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

import faults  # noqa: E402
import reduce  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]
TRACE = HERE / "testdata" / "two_steps.xplane.txt"


# -- trace reduction -------------------------------------------------------

def test_union_clip_gaps():
    busy = reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert reduce.clip(busy, 2, 8) == [(2, 3), (5, 8)]
    assert reduce.gaps(reduce.clip(busy, 2, 8), 2, 10) == [(3, 5), (8, 10)]


def test_reduce_events_hand_made():
    devices = {"/device:TPU:0": {
        "ops": [("a", 10, 20), ("b", 15, 30), ("c", 50, 60)],
        "modules": [("jit_x", 10, 30), ("jit_y", 50, 60)]}}
    spans = [("serve.step", 0, 100), ("serve.readback", 30, 45)]
    red = reduce.reduce_events((0, 100), spans, devices, n_devices=1)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["programs"] == {"jit_x": pytest.approx(20e-9),
                               "jit_y": pytest.approx(10e-9)}
    names = [g[0] for g in red["idle_gaps"]]
    assert red["idle_gaps"][0][1] == pytest.approx(40e-9)
    assert names[0] == "serve.step after jit_y"
    assert "serve.readback after jit_x" in names


def test_reduce_trace_file(tmp_path):
    """A trace file laid out as the profiler writes a TPU's (host plane
    with the benchmark's spans, ``/device:TPU:0`` with its module and op
    lines), two serve steps: busy time is the union of the op intervals,
    idle gaps are named by the innermost host span."""
    from jax.profiler import ProfileData
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TRACE.read_text()))
    red = reduce.reduce_trace(str(tmp_path), n_devices=1)
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(30e-6)      # 5-25 us and 45-55 us
    assert red["programs"] == {"jit_scatter": pytest.approx(20e-6),
                               "jit_paged_attention": pytest.approx(10e-6)}
    assert red["idle_gaps"] == [
        ["serve.readback after jit_paged_attention", pytest.approx(45e-6)],
        ["serve.step after jit_scatter", pytest.approx(20e-6)],
        ["serve.step after window start", pytest.approx(5e-6)]]
    assert red["top_programs"][0][0] == "jit_scatter"


# -- work counts -------------------------------------------------------------

def test_paged_attention_work():
    # two sequences of 100 and 28 rows, 16 heads of 128, 16 kv heads, bf16
    flops, nbytes = work.paged_attention([100, 28], 16, 16, 128, 2)
    assert flops == 4 * 16 * 128 * 128
    assert nbytes == 2 * 128 * 16 * 128 * 2 + 2 * 2 * 16 * 128 * 4
    t, bound = work.least_time(flops, nbytes, PEAKS)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_serve_decode_step_work():
    flops, nbytes = work.serve_decode_step([10], 2048, 16, 16, 128, 2, 4)
    af, ab = work.paged_attention([10], 16, 16, 128, 2)
    assert flops - af == 2 * 2048 * 48 * 128
    assert nbytes - ab == 2048 * 48 * 128 * 4 + 2048 * 4 + 2 * 16 * 128 * 2


def test_mamba2_flops_per_token():
    cfg = {"d_model": 1024, "ssm_state": 128, "ssm_headdim": 64,
           "ssm_expand": 2, "ssm_chunk": 128, "d_conv": 4, "n_layers": 48,
           "vocab": 50280}
    proj = 2 * (1024 * (4096 + 256 + 32) + 2048 * 1024)
    conv = 2 * 4 * (2048 + 256)
    ssd = 2 * 128 * 128 + 2 * 32 * 128 * 64 + 4 * 32 * 64 * 128
    want = 3 * (48 * (proj + conv + ssd) + 2 * 1024 * 50280)
    assert work.mamba2_train_flops_per_token(cfg) == want


# -- traffic ---------------------------------------------------------------

def test_every_seed_offers_the_same_lengths():
    mix = json.loads((HERE / "traffic" / "churn.json").read_text())
    n = mix["block"]

    def block(seed):
        it = traffic.requests(mix, seed)
        return [next(it) for _ in range(n)]
    a, b = block(1), block(2 ** 40 + 17)
    assert a != b
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert all(32 <= p <= 2048 and 2 <= o <= 256 for p, o in a)


# -- page schedule ---------------------------------------------------------

def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("sizes", ["full", "smoke"])
def test_longctx_lengths_fit_their_share_of_the_pool(sizes):
    """No longctx sequence can outgrow ``n_pages / max_batch`` pages: the
    other sequences never hold more than their share, so every admission
    fits and no ``append_token`` finds the pool empty, at any pace."""
    mix = _mix("longctx")
    if sizes == "smoke":
        mix = dict(mix, **mix["smoke"])
    pool = mix["pool"]
    share = pool["n_pages"] * pool["page_size"] // mix["batching"]["max_batch"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= share


@pytest.mark.parametrize("name,seed,iterations", [
    ("longctx", 1, 20000), ("longctx", 99, 20000),
    ("longctx", 7400000137, 20000),
    ("churn", 1, 4000), ("churn", 7400000109, 4000)])
def test_page_schedule_never_starves(name, seed, iterations):
    """The driver's pool, loop and backlog at full pool size, driven
    through ``ServeLoop.step`` with a decode step that does nothing, far
    past what a window reaches at any pace (20000 iterations is 1 ms an
    iteration over a 20 s window): no allocation ever fails.  Page GC
    never runs in longctx, and all eight slots decode every iteration."""
    from repro.models.config import ModelConfig
    serve = run.load_module(HERE / "drivers" / "serve_paged.py")
    mix = _mix(name)
    cfg = ModelConfig(name="pages", family="dense", n_layers=1, d_model=8,
                      n_heads=1, kv_heads=1)
    sv = serve._Serve(cfg, mix, seed, None, None, record=False)
    batches = []
    for _ in range(iterations):
        sv.top_up()
        sv.loop.step(lambda ids: batches.append(len(ids)))
    assert sv.cache.alloc_failures == 0
    assert len(sv.loop.done) > 0
    if name == "longctx":
        assert sv.loop.compaction_steps == 0
        assert set(batches) == {mix["batching"]["max_batch"]}


# -- the harness -----------------------------------------------------------

def test_manifest_names_files():
    m = run.load_manifest()
    for w in m["workloads"]:
        cell = run.find_cell(m, w["name"])
        assert (HERE / "drivers" / f"{cell.traffic['driver']}.py").exists()
        assert cell.per_layer, w["name"]
        for metric in cell.per_layer:
            assert (HERE / "metrics" / f"{metric['name']}.py").exists()


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def test_manifest_keeps_the_contract():
    import re
    m = run.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    rs = m["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"])
        assert c["file"].startswith("benchmarks/chip/")
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in m["end_to_end"] + m["per_layer"]:
        assert re.match(NAME, e["name"]) and re.match(UNIT, e["unit"])
        assert e["better"] in ("lower", "higher")
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] in (
            "host_clock", "device_trace")
    for e in m["per_layer"]:
        assert e["moves"] in e2e
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in m["workloads"]:
        assert re.match(NAME, w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)


def test_run_without_a_chip_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "olmo1b-serve-churn", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


CELLS = ["olmo1b-serve-churn", "olmo1b-serve-longctx", "mamba2-370m-train"]


def _rehearse(cell, trace=False, control=False, patch=None, seconds=1.0):
    m = run.load_manifest()
    return run.run_cell(run.find_cell(m, cell), seed=2 ** 40 + 5,
                        seconds=seconds, trace=trace, control=control,
                        smoke=True, require_tpu=False, patch=patch)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell):
    r = _rehearse(cell, trace=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["window_compiles"] == 0
    assert list(r)[-1] == "checks"
    assert r["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = _rehearse(cell, control=True)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell,fault", [
    ("olmo1b-serve-churn", f) for f in faults.SERVE] + [
    ("mamba2-370m-train", f) for f in faults.TRAIN])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.serving.kvcache import PagedKVCache
    from repro.train import step as step_mod
    for name in ("write_token_kv", "attend", "compact"):
        monkeypatch.setattr(PagedKVCache, name, getattr(PagedKVCache, name))
    monkeypatch.setattr(step_mod, "build_train_step",
                        step_mod.build_train_step)
    if fault != "compaction_misplaced":
        r = _rehearse(cell, patch=getattr(faults, fault))
        assert not r["correct"], r["checks"]
        return
    # a misplaced page shows only where the window compacts, and a busy
    # CPU may fit too few iterations into a second for any sequence to
    # finish: lengthen the window until it holds a compaction
    compact = PagedKVCache.compact
    for seconds in (1.0, 3.0, 9.0):
        PagedKVCache.compact = compact
        r = _rehearse(cell, trace=True, patch=faults.compaction_misplaced,
                      seconds=seconds)
        if r["metrics"].get("sched.compact_share", {}).get("value"):
            break
    assert r["metrics"]["sched.compact_share"]["value"] > 0, seconds
    assert not r["correct"], r["checks"]
