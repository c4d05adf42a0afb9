"""Driver ``train_step``: the training entry's jitted step
(``repro.train.step.build_train_step``, parameters and optimizer state
donated) on a data mesh over the cell's chips, fed one seeded batch of
distinct rows per step from the host and its loss read back each step, as
``repro.launch.train`` does.

Set-up builds the step and its state once, from the seed, and drives it
through its first three steps with the window's own call and feed; the
window then goes on with that same object.  Those three steps are what
the reference (``refs/mamba2``, float32 at highest precision) follows:
the first gradient's norm per layer of every leaf as the optimizer holds
it after step 1 (mu / (1 - b1)), and each leaf's change after step 3, as
far as step 4 keeps them.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import weights
import work

CHECK_STEPS = 3


def batch(seed: int, step: int, b: int, s: int, vocab: int) -> Dict:
    """Step ``step``'s rows: seeded tokens, each row its own."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 11, step])
    tok = rng.integers(0, vocab, size=(b, s + 1), dtype=np.int32)
    return {"tokens": tok[:, :-1], "targets": tok[:, 1:],
            "positions": np.tile(np.arange(s, dtype=np.int32), (b, 1))}


def _norms(tree, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """Norm of every leaf, per layer where the leaf stacks layers."""
    def one(path, a):
        a = a.astype(jnp.float32) * scale
        if "layers" in [getattr(p, "key", None) for p in path]:
            return jnp.sqrt(jnp.sum(jnp.square(a),
                                    axis=tuple(range(1, a.ndim))))
        return jnp.sqrt(jnp.sum(jnp.square(a)))[None]
    out = jax.jit(lambda t: jax.tree_util.tree_map_with_path(one, t))(tree)
    flat, _ = jax.tree_util.tree_flatten_with_path(out)
    return {"/".join(getattr(p, "key", str(p)) for p in path):
            np.asarray(v) for path, v in flat}


def _diff_norms(a, b) -> Dict[str, np.ndarray]:
    return _norms(jax.jit(lambda x, y: jax.tree.map(
        lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32), x, y))(
            a, b))


def _gap(got: Dict, want: Dict, keep: Dict) -> float:
    """Worst leaf: |‖got‖ − ‖want‖| over the larger of ‖want‖ and the
    median leaf's ‖want‖, over the leaves ``keep`` marks."""
    med = float(np.median(np.concatenate([want[k][keep[k]] for k in want])))
    worst = 0.0
    for k in want:
        w, g = want[k][keep[k]], got[k][keep[k]]
        if w.size:
            worst = max(worst, float(np.max(np.abs(g - w)
                                            / np.maximum(w, med))))
    return worst


def _reference(ctx, cfgj, mix, abstract, dtype) -> Dict:
    """The reference's three steps from the same seed, layer by layer
    under checkpointing; returns its norms."""
    from refs import mamba2 as ref
    opt = cfgj["optimizer"]
    params = weights.make(abstract, weights.seed_key(ctx.seed))
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    grad = jax.jit(jax.grad(
        lambda p, t, y: ref.loss(p, t, y, cfgj, dtype)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    rows = mix["ref_rows"]          # rows per block: the mean over blocks
    blocks = mix["batch"] // rows   # of equal size is the batch's mean
    hyper = {k: v for k, v in opt.items() if k != "kind"}
    update = jax.jit(ref.adamw, static_argnums=(4,))
    g_norms = None
    for step in range(1, CHECK_STEPS + 1):
        bt = batch(ctx.seed, step, mix["batch"], mix["seq"], cfgj["vocab"])
        g = None
        for i in range(blocks):
            sl = slice(i * rows, (i + 1) * rows)
            with jax.default_matmul_precision("highest"):
                gi = grad(params, bt["tokens"][sl], bt["targets"][sl])
            g = gi if g is None else add(g, gi)
            del gi
        g = jax.tree.map(lambda x: x / blocks, g)
        if step == 1:
            g_norms = _norms(g)
        params, mu, nu = update(params, g, mu, nu, step, hyper)
        del g
    return {"grad": g_norms,
            "change": _diff_norms(params, p0)}


def run(ctx) -> Dict:
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.train.optimizer import AdamWConfig, init_state
    from repro.train.step import TrainConfig, build_train_step
    cfgj, mix = ctx.config(), ctx.mix()
    cfg = get_config(cfgj["model"], smoke=ctx.smoke)
    b, s = mix["batch"], mix["seq"]
    opt = cfgj["optimizer"]
    mesh = make_host_mesh(devices=len(ctx.devices))
    tc = TrainConfig(adamw=AdamWConfig(
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]))
    fn, in_sh, out_sh, abstract = build_train_step(cfg, mesh, b, s, tc)
    step_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0, 1))
    key = weights.seed_key(ctx.seed)
    params = weights.make(abstract[0], key, shardings=in_sh[0])
    opt_state = jax.jit(lambda p: init_state(p, tc.adamw),
                        out_shardings=in_sh[1])(params)

    def one(step):
        nonlocal params, opt_state
        bt = {k: jnp.asarray(v) for k, v in
              batch(ctx.seed, step, b, s, cfgj["vocab"]).items()}
        with jax.profiler.TraceAnnotation("train.step"):
            params, opt_state, m = step_fn(params, opt_state, bt)
            with jax.profiler.TraceAnnotation("train.loss_readback"):
                return float(m["loss"])

    for step in range(1, CHECK_STEPS + 1):
        one(step)
        if step == 1:
            g_norms = _norms(opt_state["mu"], 1.0 / (1.0 - opt["b1"]))
    p0 = weights.make(abstract[0], key, shardings=in_sh[0])
    change = _diff_norms(params, p0)
    del p0

    step, window_losses, step_s = CHECK_STEPS + 1, [], []
    ctx.start_window()
    while ctx.window_open():
        t0 = time.perf_counter()
        window_losses.append(one(step))
        step_s.append(time.perf_counter() - t0)
        step += 1
    window_s = ctx.end_window()
    del params, opt_state            # the program's state goes first

    t0 = time.perf_counter()
    want = _reference(ctx, cfgj, mix, abstract[0], jnp.float32)
    if ctx.control:
        low = _reference(ctx, cfgj, mix, abstract[0],
                         jnp.dtype(mix["limits"]["control_dtype"]))
        g_norms, change = low["grad"], low["change"]
    med = float(np.median(np.concatenate(list(want["grad"].values()))))
    keep = {k: v >= mix["limits"]["still_leaf"] * med
            for k, v in want["grad"].items()}
    lim = mix["limits"]
    # the loss is not compared: at initialisation it sits near log(vocab)
    # in any precision, so the control cannot fail it (see PERF.md)
    checks = {
        "grad_norm_gap": {"value": _gap(g_norms, want["grad"], keep),
                          "limit": lim["grad_norm_gap"]},
        "change_norm_gap": {"value": _gap(change, want["change"], keep),
                            "limit": lim["change_norm_gap"]},
    }
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    failed = sum(not np.isfinite(x) for x in window_losses)
    tokens = len(window_losses) * b * s
    record = {"window_s": window_s, "tokens": tokens,
              "steps": len(window_losses),
              "step_s_median": float(np.median(step_s or [np.nan])),
              "step_s_max": float(np.max(step_s or [np.nan])),
              "check_s": time.perf_counter() - t0,
              "flops_per_token": work.mamba2_train_flops_per_token(cfgj)}
    return {"correct": ok and failed == 0, "attempted": len(window_losses),
            "failed": failed, "record": record, "checks": checks,
            "end_to_end": {"train_tok_s": tokens / window_s}}
