"""The ``train_model`` cells rehearsed on the CPU at SMOKE widths, no chip:
each is correct against its plain reference, reports its per-layer
metrics that need no device trace, and comes out not correct with the
reference one precision lower in the program's place (the control) and
under every training fault of ``faults.py``.  Also the trace reading of
``drivers/train_model`` and the work counts of ``work_models``.

Run from the repository root on the CPU:
  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(REPO / "src"))

import faults  # noqa: E402
import run  # noqa: E402
import work_models  # noqa: E402

CELLS = ["nemotron3nano-train-8k", "olmo1b-train-dp4"]
DRIVER = run.load_module(HERE / "drivers" / "train_model.py")


def _rehearse(cell, trace=False, control=False, patch=None):
    m = run.load_manifest()
    return run.run_cell(run.find_cell(m, cell), seed=2 ** 40 + 9,
                        seconds=0.5, trace=trace, control=control,
                        smoke=True, require_tpu=False, patch=patch)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell):
    r = _rehearse(cell, trace=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["window_compiles"] == 0
    assert list(r)[-1] == "checks"
    assert r["metrics"]["mfu.train_model"]["value"] > 0
    if cell.startswith("nemotron"):
        assert set(r["checks"]) == {"grad_norm_gap", "change_norm_gap",
                                    "pairs_gap"}
        ratio = r["metrics"]["moe.load_ratio"]["value"]
        assert 1.0 <= ratio <= 4.0      # SMOKE holds 4 experts


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = _rehearse(cell, control=True)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in faults.TRAIN])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.train import step as step_mod
    monkeypatch.setattr(step_mod, "build_train_step",
                        step_mod.build_train_step)
    r = _rehearse(cell, patch=getattr(faults, fault))
    assert not r["correct"], r["checks"]


def exchange_dropped(chips: int = 4):
    """Training on a data mesh of ``chips``: the gradient exchange between
    chips is dropped, so each chip updates its own part of every leaf (a
    ``chips``-th of its first axis, with its AdamW moments) from the
    gradient of its own rows alone."""
    def wrap(fn):
        def step(params, opt_state, batch):
            n = batch["tokens"].shape[0] // chips
            outs = [fn(params, opt_state,
                       {k: jnp.concatenate([v[i * n:(i + 1) * n]] * chips)
                        for k, v in batch.items()}) for i in range(chips)]

            def own(*leaves):
                a = leaves[0]
                if a.ndim == 0 or a.shape[0] % chips:
                    return a
                m = a.shape[0] // chips
                return jnp.concatenate([x[i * m:(i + 1) * m]
                                        for i, x in enumerate(leaves)])
            return (jax.tree.map(own, *[o[0] for o in outs]),
                    jax.tree.map(own, *[o[1] for o in outs]), outs[0][2])
        return step
    faults._wrap_step(wrap)


def test_dropped_gradient_exchange_is_not_correct(monkeypatch):
    from repro.train import step as step_mod
    monkeypatch.setattr(step_mod, "build_train_step",
                        step_mod.build_train_step)
    r = _rehearse("olmo1b-train-dp4", patch=exchange_dropped)
    assert not r["correct"], r["checks"]


def test_cut_applies_the_configuration_file():
    cfgj = json.loads((HERE / "configs" / "nemotron3-nano-30b-a3b.json")
                      .read_text())
    cfg = DRIVER.model_config(cfgj, smoke=False)
    assert (cfg.n_layers, cfg.held_experts, cfg.n_experts, cfg.vocab) == (
        7, 8, 128, 16384)
    assert cfg.param_count() == pytest.approx(528.09e6, rel=1e-4)
    assert cfg.d_model == cfgj["hidden_size"] and cfg.d_ff == cfgj[
        "moe_intermediate_size"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_projection_seeds_at_its_whole_fan_in(cell):
    """``fan_in`` puts the attention output projection (heads, head_dim,
    d) at 1/sqrt(heads x head_dim), which ``weights.make`` alone seeds at
    1/sqrt(heads), and leaves every other leaf as seeded."""
    import numpy as np
    import weights
    from repro.models import get_model
    cfgj = run.find_cell(run.load_manifest(), cell).config
    cfg = DRIVER.model_config(dict(cfgj, **cfgj["smoke"]), smoke=True)
    seeded = weights.make(get_model(cfg).init(cfg, abstract=True),
                          weights.seed_key(7))
    fixed = DRIVER.fan_in(seeded)
    flat = jax.tree_util.tree_flatten_with_path(fixed)[0]
    before = dict(jax.tree_util.tree_flatten_with_path(seeded)[0])
    projections = 0
    for path, a in flat:
        if [p.key for p in path[-2:]] == ["attn", "wo"]:
            projections += 1
            want = 1 / np.sqrt(a.shape[-3] * a.shape[-2])
            assert float(jnp.std(a)) == pytest.approx(want, rel=0.1)
        else:
            assert a is before[path]
    assert projections >= 1


def test_only_collectives_and_kernels_from_op_lines():
    """Collective time not covered by any other op (a loop that holds
    them does not cover them), and each kernel's time, on hand-made op
    lines of two chips."""
    coll = [(0, 10), (20, 30)]
    rest = [(5, 8), (25, 40)]
    assert DRIVER._minus(coll, rest) == 5 + 2 + 5
    ops = [("while.3", 0, 100), ("fusion.1", 0, 40), ("gmm.2", 40, 50),
           ("all-gather-done.4", 45, 70), ("tgmm.1", 70, 80),
           ("splash_mqa_fwd_residuals.1", 80, 90)]
    got = DRIVER.op_times((10, 95), {"/device:TPU:0": {"ops": ops},
                                     "/device:TPU:1": {"ops": ops[1:]}}, 2)
    assert got["collective_only"] == pytest.approx(20e-9)
    assert got["gmm"] == pytest.approx(20e-9)
    assert got["attn"] == pytest.approx(10e-9)
    pat = DRIVER.KERNELS
    assert pat["gmm"].match("gmm.2") and pat["gmm"].match("tgmm.11")
    assert not pat["gmm"].match("fusion.3")
    assert pat["attn"].match("splash_mqa_dkv_no_residuals.1")
    assert DRIVER.COLLECTIVE.search("all-gather-start.4")
    assert not DRIVER.COLLECTIVE.search("fusion.12")


def test_nemotron_flops_per_token():
    """The forward's 589 MFLOP a token at 8k: 3 Mamba-2 layers of 80.9 M,
    3 expert layers of 48.1 M (router, shared expert and the held share of
    6 of 128 routed experts), attention 113.9 M, the head 88.1 M."""
    cfgj = json.loads((HERE / "configs" / "nemotron3-nano-30b-a3b.json")
                      .read_text())
    per = work_models.nemotron_h_layer_flops(cfgj, 8192)
    d, di, conv = 2688, 4096, 4096 + 2 * 8 * 128
    mamba = (2 * d * (di + conv + 64) + 2 * di * d + 2 * 4 * conv
             + 2 * 128 * 128 * 8 + 2 * 64 * 128 * 64 + 4 * 64 * 64 * 128)
    assert per["M"] == mamba
    assert per["E"] == 2 * d * 128 + 6 * 8 / 128 * 4 * d * 1856 \
        + 4 * d * 3712
    assert per["*"] == 2 * d * 36 * 128 + 2 * 4096 * d + 4 * 4096 * 4096
    fwd = 3 * mamba + 3 * per["E"] + per["*"] + 2 * d * 16384
    assert work_models.nemotron_h_train_flops_per_token(cfgj, 8192) == \
        3 * fwd
    assert fwd == pytest.approx(588.85e6, rel=1e-4)


def test_grouped_matmul_and_attention_work():
    flops, nbytes = work_models.gmm_train(1000, 8, 2688, 1856, calls=2)
    assert flops == 12 * 1000 * 2688 * 1856
    assert nbytes == 6 * (2 * 8 * 2688 * 1856 * 2 + 1000 * (2688 + 1856) * 2)
    flops, nbytes = work_models.attention_train(2, 8192, 32, 2, 128)
    assert flops == 3 * 4 * 2 * 32 * 128 * 8192 * 8192 / 2
    q, kv = 2 * 8192 * 32 * 128 * 2, 2 * 2 * 8192 * 2 * 128 * 2
    assert nbytes == 6 * q + 3 * kv
