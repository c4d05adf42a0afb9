"""Per-architecture smoke tests (reduced configs): one forward + train
step on CPU asserting shapes + finiteness; decode for decoder archs;
family-specific math checks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import get_model
from repro.train.data import synthetic_batch
from repro.train.optimizer import AdamWConfig, apply_updates, init_state


def _batch(cfg, b=2, s=32):
    return {k: jnp.asarray(v)
            for k, v in synthetic_batch(cfg, 0, b, s).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = model.forward(params, batch, cfg)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
    opt = init_state(params, AdamWConfig())
    new_params, new_opt = apply_updates(params, grads, opt, AdamWConfig())
    # a step actually changes the params
    delta = sum(float(jnp.sum(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(new_params), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", [a for a in ARCHS if a not in (
    "hubert_xlarge", "nemotron3_nano_30b_a3b")])
def test_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(1))
    b = 2
    cache = (model.init_cache(cfg, b) if cfg.family == "ssm"
             else model.init_cache(cfg, b, 64))
    lengths = jnp.array([3, 5], jnp.int32)
    logits, cache2 = model.decode_step(
        params, cache, lengths, jnp.ones((b, 1), jnp.int32), cfg)
    assert logits.shape == (b, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


def test_prefill_decode_consistency_dense():
    """Greedy decode over a cache must match teacher-forced forward."""
    cfg = dataclasses.replace(get_config("phi3_mini_3_8b", smoke=True),
                              compute_dtype=jnp.float32)
    model = get_model(cfg)
    params = model.init(cfg, jax.random.PRNGKey(2))
    b, s = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab)
    pos = jnp.tile(jnp.arange(s)[None], (b, 1))
    full = model.forward(params, {"tokens": tokens, "positions": pos}, cfg)
    # feed tokens one by one through the cache
    cache = model.init_cache(cfg, b, 16)
    outs = []
    for t in range(s):
        lengths = jnp.full((b,), t, jnp.int32)
        lg, cache = model.decode_step(params, cache, lengths,
                                      tokens[:, t:t + 1], cfg)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-3, rtol=2e-3)


def _ssd_against_recurrence(groups):
    """The grouped chunked SSD against the step-by-step recurrence, run
    group by group over the heads that read each group's B and C."""
    from repro.kernels.ref import ssd_scan_ref
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(0)
    B, S, H, P, N, G = 2, 32, 6, 4, 5, groups
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.9, size=(B, S, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 1.5, size=(H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    y1, s1 = ssd_chunked(x, dt, a, bm, cm, chunk=8)
    r = H // G
    parts = [ssd_scan_ref(x[:, :, g * r:(g + 1) * r],
                          dt[:, :, g * r:(g + 1) * r], a[g * r:(g + 1) * r],
                          bm[:, :, g], cm[:, :, g]) for g in range(G)]
    y2 = jnp.concatenate([y for y, _ in parts], axis=2)
    s2 = jnp.concatenate([st for _, st in parts], axis=1)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)


def test_ssd_chunked_matches_recurrence():
    _ssd_against_recurrence(1)


def test_grouped_ssd_chunked_matches_recurrence():
    _ssd_against_recurrence(3)


def test_mrope_sections_differ_from_rope():
    from repro.models.modules import apply_mrope, apply_rope
    x = jnp.ones((1, 4, 2, 24))
    pos3 = jnp.stack([jnp.arange(4), jnp.arange(4) * 2,
                      jnp.arange(4) * 3], axis=-1)[None]
    out = apply_mrope(x, pos3, sections=(4, 4, 4))
    base = apply_rope(x, pos3[..., 0])
    assert out.shape == x.shape
    assert not np.allclose(np.asarray(out), np.asarray(base))


def test_moe_routes_topk_and_preserves_scale():
    cfg = get_config("grok_1_314b", smoke=True)
    from repro.models.modules import ffn, ffn_specs, materialize
    params = materialize(ffn_specs(cfg), jax.random.PRNGKey(0), False)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32).astype(cfg.compute_dtype)
    y = ffn(params, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y).sum()) > 0


def test_param_count_sanity():
    # full-config param counts land near the advertised sizes
    assert abs(get_config("grok_1_314b").param_count() / 1e9 - 314) < 25
    assert abs(get_config("phi3_mini_3_8b").param_count() / 1e9 - 3.8) < 0.8
    assert abs(get_config("olmo_1b").param_count() / 1e9 - 1.2) < 0.4
    assert abs(get_config("mamba2_370m").param_count() / 1e6 - 370) < 120
