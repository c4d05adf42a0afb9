"""The Nemotron-H model (``models.nemotron_h``), its expert layer
(``models.moe``) and its training attention (``kernels.train_attention``)
against plain references, on the CPU at SMOKE widths, in float32 on
seeded weights.

The program and ``benchmarks/chip/refs/nemotron_h`` compute the same
float32 mathematics in different orders (chunked against minimal-listing
SSD, grouped matmul against dense masked experts, a sorted dispatch
against none), so they agree to float32 rounding: about 1e-6 of a value
per operation, a few 1e-5 after seven layers and a backward pass.  The
tolerances sit an order of magnitude above that and far below any change
in the mathematics (a dropped pair, a wrong group, a missing bias moves
values by 1e-2 or more).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import weights  # noqa: E402
from refs import nemotron_h as ref  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import get_model, moe  # noqa: E402
from repro.models.modules import materialize  # noqa: E402

F32 = jnp.float32


def _cfg(**kw):
    return dataclasses.replace(get_config("nemotron3_nano_30b_a3b",
                                          smoke=True),
                               compute_dtype=F32, **kw)


def _ref_cfg():
    j = json.loads((CHIP / "configs" / "nemotron3-nano-30b-a3b.json")
                   .read_text())
    return dict(j, **j["smoke"])


def _params(cfg, seed=3):
    model = get_model(cfg)
    return weights.make(model.init(cfg, abstract=True),
                        weights.seed_key(seed))


def _tokens(cfg, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, cfg.vocab, size=(b, s + 1), dtype=np.int32)
    return {"tokens": jnp.asarray(tok[:, :-1]),
            "targets": jnp.asarray(tok[:, 1:]),
            "positions": jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))}


def test_smoke_keeps_the_pattern_and_a_share_of_experts():
    cfg = get_config("nemotron3_nano_30b_a3b", smoke=True)
    assert cfg.pattern() == "MEMEM*E"
    assert 1 < cfg.ssm_groups < cfg.ssm_heads
    assert cfg.held_experts < cfg.n_experts
    full = get_config("nemotron3_nano_30b_a3b")
    assert full.pattern().count("M") == 23 and full.pattern().count("*") == 6
    assert full.param_count() == pytest.approx(31.58e9, rel=1e-3)


def test_model_matches_reference():
    """Logits, loss, routing counts and every leaf's gradient."""
    cfg, rc = _cfg(), _ref_cfg()
    model = get_model(cfg)
    params = _params(cfg)
    batch = _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(
            model.loss_and_stats, has_aux=True)(params, batch, cfg)
        logits = model.forward(params, batch, cfg)
        want_logits, _ = ref.forward(params, batch["tokens"], rc)
        (want_loss, want_stats), want_grads = jax.value_and_grad(
            ref.loss, has_aux=True)(params, batch["tokens"],
                                    batch["targets"], rc)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_array_equal(stats["moe_pairs"], want_stats["moe_pairs"])
    np.testing.assert_array_equal(stats["moe_load"], want_stats["moe_load"])
    assert int(stats["moe_pairs"].sum()) > 0
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        w = want[path]
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_train_step_moves_each_selection_bias_against_its_load():
    """After a step every expert layer's selection bias is its value
    before, moved by the rate against the step's load per expert (no
    gradient and no weight decay), as the reference's ``rebias`` moves
    it; the configuration file and the program give the same rate."""
    from repro.launch.mesh import make_host_mesh
    from repro.train.optimizer import AdamWConfig, init_state
    from repro.train.step import TrainConfig, build_train_step
    cfg, rc = _cfg(), _ref_cfg()
    assert moe.BIAS_RATE == rc["training"]["router_bias_update_rate"]
    tc = TrainConfig(adamw=AdamWConfig(lr=1e-3, weight_decay=0.1))
    fn, *_ = build_train_step(cfg, make_host_mesh(devices=1), 2, 32, tc)
    params = _params(cfg)
    batch = _tokens(cfg, b=2, s=32)
    before = ref.biases(params, rc)
    with jax.default_matmul_precision("highest"):
        _, want = ref.loss(params, batch["tokens"], batch["targets"], rc)
        new, _, metrics = jax.jit(fn)(params, init_state(params, tc.adamw),
                                      batch)
    np.testing.assert_array_equal(metrics["moe_load"], want["moe_load"])
    assert metrics["moe_load"].sum(axis=1).tolist() == [2 * 32 * cfg.top_k] * 3
    moved = ref.rebias(new, before, want["moe_load"], rc)
    for name, b in before.items():
        got = new["blocks"][name]["moe"]["router_bias"]
        np.testing.assert_array_equal(
            got, moved["blocks"][name]["moe"]["router_bias"])
        step = np.abs(np.asarray(got) - b)
        assert np.all(np.isclose(step, moe.BIAS_RATE)
                      | (step == 0)) and step.max() > 0


def _layer_params(cfg, seed=5):
    return weights.make(materialize(moe.specs(cfg), None, True, F32),
                        weights.seed_key(seed))


def _shared(p, x):
    h = jnp.square(jax.nn.relu(x @ p["shared_up"]))
    return h @ p["shared_down"]


def test_chip_shares_add_up_to_the_whole_layer():
    """Each chip holds its own 4 of 16 experts and routes over all 16;
    their parts, with the shared expert counted once, are the uncut
    layer's output."""
    whole = _cfg(experts_held=0)
    share = _cfg()
    p = _layer_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, whole.d_model), F32)
    with jax.default_matmul_precision("highest"):
        want, want_counts = moe.moe(p, x, whole)
        want_pairs = want_counts["moe_pairs"]
        n = whole.n_experts // share.held_experts
        total, pairs = -(n - 1) * _shared(p, x), []
        for c in range(n):
            sl = slice(c * share.held_experts, (c + 1) * share.held_experts)
            pc = dict(p, w_up=p["w_up"][:, sl], w_down=p["w_down"][:, sl])
            y, counts = moe.moe(pc, x, share,
                                first_expert=c * share.held_experts)
            np.testing.assert_array_equal(counts["moe_load"],
                                          want_counts["moe_load"])
            total, pairs = total + y, pairs + [counts["moe_pairs"]]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(pairs), want_pairs)
    assert int(want_pairs.sum()) == 2 * 32 * whole.top_k


def test_no_token_dropped_under_a_skewed_router():
    """A selection bias that sends every token to the same three held
    experts: all of them are computed (no capacity), as the dense
    reference computes them."""
    cfg, rc = _cfg(), _ref_cfg()
    p = _layer_params(cfg)
    p["router_bias"] = p["router_bias"].at[:cfg.top_k].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model), F32)
    with jax.default_matmul_precision("highest"):
        y, counts = moe.moe(p, x, cfg)
        want, want_counts = ref.experts(p, x, rc)
    pairs, want_pairs = counts["moe_pairs"], want_counts["moe_pairs"]
    assert pairs.tolist() == [128] * cfg.top_k + [0] * (
        cfg.held_experts - cfg.top_k)
    np.testing.assert_array_equal(pairs, want_pairs)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def _naive(q, k, v):
    b, s, h, d = q.shape
    g = h // k.shape[2]
    kk, vv = (jnp.repeat(a, g, axis=2) for a in (k, v))
    sc = jnp.einsum("bqhd,bthd->bhqt", q, kk) / np.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqt,bthd->bqhd", jax.nn.softmax(sc, -1), vv)


def test_training_attention_matches_naive_forward_and_backward():
    """The splash path (interpret mode here) against softmax attention
    over the S x S scores, at S 384 (three blocks of 128, so that the
    causal mask skips and splits blocks), GQA 4/2."""
    from repro.kernels.train_attention import block_for, causal_attention
    b, s, h, kh, d = 1, 384, 4, 2, 128
    assert block_for(s) == 128
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v = (jax.random.normal(kk, (b, s, n, d), F32)
               for kk, n in zip(keys, (h, kh, kh)))
    ct = jax.random.normal(keys[3], (b, s, h, d), F32)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(ct)
    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: causal_attention(q, k, v, interpret=True))
        want = run(_naive)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)
