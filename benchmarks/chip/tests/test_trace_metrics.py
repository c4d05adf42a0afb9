"""The per-layer metrics that read the program's own spans and counters
(``repro.obs.spans``), on the CPU: a traced SMOKE rehearsal of each serve
cell reports every such metric listed for it, non-null and in range, and
each reads nothing, without raising, from a program that has no recorder.

Run from the repository root on the CPU:
  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

import run  # noqa: E402

# metric -> (the serve cells that report it, lowest value, highest value);
# a reading must lie above the lowest value, or at it for the metrics in
# AT_LOWEST
METRICS = {
    "sched.host_ms": (("olmo1b-serve-churn", "olmo1b-serve-longctx"),
                      0.0, math.inf),
    "cache.host_ms": (("olmo1b-serve-churn", "olmo1b-serve-longctx"),
                      0.0, math.inf),
    "cache.compact_host_ms": (("olmo1b-serve-churn",), 0.0, math.inf),
    "cache.compact_copy_ratio": (("olmo1b-serve-churn",), 1.0, math.inf),
    "sched.batch_occupancy": (("olmo1b-serve-churn", "olmo1b-serve-longctx"),
                              0.0, 100.0),
}
# a compaction that moves only the pages it must copies exactly one page
# per page relocated
AT_LOWEST = {"cache.compact_copy_ratio"}
SERVE = ["olmo1b-serve-churn", "olmo1b-serve-longctx"]


def test_manifest_lists_each_metric_for_its_cells():
    m = run.load_manifest()
    per_layer = {e["name"]: e for e in m["per_layer"]}
    for name, (cells, _, _) in METRICS.items():
        assert sorted(per_layer[name]["workloads"]) == sorted(cells)
        assert (HERE / "metrics" / f"{name}.py").exists()


@pytest.fixture(scope="module", params=SERVE)
def rehearsal(request):
    cell = run.find_cell(run.load_manifest(), request.param)
    return request.param, run.run_cell(
        cell, seed=2 ** 40 + 11, seconds=1.0, trace=True, smoke=True,
        require_tpu=False)


def test_traced_rehearsal_reports_each_metric_in_range(rehearsal):
    cell, r = rehearsal
    assert r["correct"], r["checks"]
    for name, (cells, lo, hi) in METRICS.items():
        if cell not in cells:
            assert name not in r["metrics"]
            continue
        value = r["metrics"][name]["value"]
        assert value is not None, (cell, name)
        above = lo <= value if name in AT_LOWEST else lo < value
        assert above and value <= hi, (cell, name, value)


def test_longctx_keeps_every_slot_full(rehearsal):
    cell, r = rehearsal
    occupancy = r["metrics"]["sched.batch_occupancy"]["value"]
    if cell == "olmo1b-serve-longctx":      # none waits: every admission fits
        assert occupancy == 100.0
    else:
        assert 0.0 < occupancy <= 100.0


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_nothing_without_the_recorder(name, monkeypatch):
    import repro.obs
    monkeypatch.delattr(repro.obs, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    metric = run.load_module(HERE / "metrics" / f"{name}.py")
    assert metric.read({}) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_nothing_from_an_empty_recorder(name):
    from repro.obs import spans
    spans.reset()
    metric = run.load_module(HERE / "metrics" / f"{name}.py")
    assert metric.read({}) is None
