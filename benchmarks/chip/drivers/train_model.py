"""Driver ``train_model``: the training entry's jitted step
(``repro.train.step.build_train_step``, parameters and optimizer state
donated) for any configuration the program lists, on a data mesh over the
cell's chips (parameters and AdamW state sharded over it), fed one seeded
batch of distinct rows per step from the host and its loss read back each
step, as ``repro.launch.train`` does.

The configuration file names the program's model (``model``); ``MODELS``
says, for each, which of the file's keys cut the program's published
config (depth, experts held, vocabulary rows), which plain reference
follows it and which work function counts its training FLOPs.
The optimizer and the limits are the traffic mix's.

The weights are seeded (``weights.make``), the attention output
projections then scaled to 1/sqrt of their whole fan-in (``fan_in``);
where the reference has a ``balance``, each expert layer's selection bias
is then set so that step 1's first row spreads evenly over the experts,
the state a trained ``e_score_correction_bias`` keeps, for the program and
the reference alike; after every step each moves against its expert's
load on the step's rows, in the program's step and in the reference's
(``rebias``).
Set-up builds the step and its state once, from the seed, and drives it
through its first three steps with the window's own call and feed; the
window then goes on with that same object.  The check, as
``train_step``'s: the reference's three steps from the same seed and rows,
float32 at highest precision, in row blocks; the first gradient's norm per
leaf (per layer where leaves stack layers) against the optimizer's
mu / (1 - b1) after step 1, and each leaf's change after step 3.  A model
with expert layers also returns its routing counts from the step: step
1's pairs per held expert of each expert layer are compared with the
reference's routing of the same rows (relative L1 gap).

Traced, the loop hands each step's routing counts to the program's
counters (``record_routing``) after the loss readback, and after the
window the driver reads the device trace's op lines (``reduce.load``): the
device seconds of the grouped matmul (``gmm``/``tgmm``) and of the
training attention (``splash_*``), and the seconds in which only
collectives run, each clipped to the window and averaged over the chips.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import re
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import reduce
import weights
import work_models
from drivers.train_step import CHECK_STEPS, _diff_norms, _gap, _norms, batch

# per model: program field <- configuration key, reference, FLOPs a token
MODELS = {
    "nemotron3-nano-30b-a3b": (
        {"n_layers": "num_hidden_layers", "experts_held": "n_routed_experts",
         "vocab": "vocab_size"},
        "nemotron_h", work_models.nemotron_h_train_flops_per_token),
    "olmo-1b": ({"n_layers": "n_layers", "vocab": "vocab"}, "olmo",
                work_models.olmo_train_flops_per_token),
}
KERNELS = {"gmm": re.compile(r"^%?t?gmm\b"),
           "attn": re.compile(r"^%?splash_")}
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|"
                        r"collective-permute")
# ops that hold others on the op line (a scanned layer loop): not compute
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")


def model_config(cfgj: Dict, smoke: bool):
    from repro.configs import get_config
    cfg = get_config(cfgj["model"], smoke=smoke)
    return dataclasses.replace(cfg, **{f: cfgj[k] for f, k in
                                       MODELS[cfgj["model"]][0].items()})


def _length(iv: List[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def _minus(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
           ) -> float:
    """Length of the sorted, disjoint intervals ``a`` outside the sorted,
    disjoint intervals ``b``."""
    inside, j = 0.0, 0
    for a0, a1 in a:
        while j < len(b) and b[j][1] <= a0:
            j += 1
        k = j
        while k < len(b) and b[k][0] < a1:
            inside += min(a1, b[k][1]) - max(a0, b[k][0])
            k += 1
    return _length(a) - inside


def device_times(trace_dir: str, n_devices: int) -> Dict:
    """``op_times`` of the trace in ``trace_dir`` ({} without a window)."""
    window, _, devices = reduce.load(reduce.find_xplane(trace_dir))
    return {} if window is None else op_times(window, devices, n_devices)


def op_times(window: Tuple[float, float], devices: Dict, n_devices: int
             ) -> Dict:
    """Seconds of each kernel of ``KERNELS`` and of collectives alone
    (no other op beside them but a loop or call that holds them), from the
    devices' op lines, clipped to the window, mean over chips."""
    names = sorted(devices)[:n_devices]
    if not names:
        return {}
    lo, hi = window
    out = dict.fromkeys(list(KERNELS) + ["collective_only"], 0.0)
    for name in names:
        ops = devices[name]["ops"]
        for k, pat in KERNELS.items():
            out[k] += _length(reduce.union(reduce.clip(
                [(a, b) for n, a, b in ops if pat.match(n)], lo, hi)))
        coll = reduce.union(reduce.clip(
            [(a, b) for n, a, b in ops if COLLECTIVE.search(n)], lo, hi))
        rest = reduce.union(reduce.clip(
            [(a, b) for n, a, b in ops if not COLLECTIVE.search(n)
             and not CONTAINER.match(n)], lo, hi))
        out["collective_only"] += _minus(coll, rest)
    return {k: v / len(names) * 1e-9 for k, v in out.items()}


def fan_in(params):
    """The seeded tree with each attention output projection ``attn/wo``
    (heads, head_dim, d) at 1/sqrt(heads x head_dim): ``weights.make``
    takes a leaf's first axis as its fan-in, here the head count alone,
    which seeds ``wo`` sqrt(head_dim) times too large (11 for 128); the
    attention layer's nearly uniform causal average then outweighs the
    residual stream, so the next expert layer's router sees nearly the
    same input for every token."""
    def one(path, a):
        keys = [getattr(p, "key", None) for p in path[-2:]]
        return a / math.sqrt(a.shape[-2]) if keys == ["attn", "wo"] else a
    return jax.tree_util.tree_map_with_path(one, params)


def _put(x, sharding):
    return jax.device_put(np.asarray(x), sharding)


def _reference(ctx, ref, cfgj, mix, vocab, initial, shardings, rows_sh,
               dtype) -> Dict:
    """The reference's three steps from the same seed, in row blocks,
    starting from ``initial()``, each expert layer's selection bias moved
    after each step where the reference balances it (``rebias``, from the
    step's pairs per expert over all blocks); returns its norms and step
    1's routing counts (or None)."""
    opt = mix["optimizer"]
    params = initial()
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    mu, nu = zeros(params), zeros(params)
    grad = jax.jit(jax.grad(
        lambda p, t, y: ref.loss(p, t, y, cfgj, dtype), has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    rows = mix["ref_rows"]          # rows per block: the mean over blocks
    blocks = mix["batch"] // rows   # of equal size is the batch's mean
    hyper = {k: v for k, v in opt.items() if k != "kind"}
    # donated, and the initial weights made again at the end: one chip
    # holds the reference's weights, moments and gradients at float32
    update = jax.jit(ref.adamw, static_argnums=(4,),
                     donate_argnums=(0, 2, 3))
    g_norms, pairs = None, None
    for step in range(1, CHECK_STEPS + 1):
        bt = batch(ctx.seed, step, mix["batch"], mix["seq"], vocab)
        g, load = None, None
        for i in range(blocks):
            sl = slice(i * rows, (i + 1) * rows)
            with jax.default_matmul_precision("highest"):
                gi, aux = grad(params, _put(bt["tokens"][sl], rows_sh),
                               _put(bt["targets"][sl], rows_sh))
            g = gi if g is None else add(g, gi)
            if step == 1 and "moe_pairs" in aux:
                n = np.asarray(aux["moe_pairs"], np.int64)
                pairs = n if pairs is None else pairs + n
            if "moe_load" in aux:
                n = np.asarray(aux["moe_load"], np.int64)
                load = n if load is None else load + n
            del gi
        g = jax.tree.map(lambda x: x / blocks, g)
        if step == 1:
            g_norms = _norms(g)
        before = (ref.biases(params, cfgj)
                  if load is not None and hasattr(ref, "rebias") else None)
        params, mu, nu = update(params, g, mu, nu, step, hyper)
        if before is not None:
            params = ref.rebias(params, before, load, cfgj)
        del g
    return {"grad": g_norms, "change": _diff_norms(params, initial()),
            "pairs": pairs}


def run(ctx) -> Dict:
    from repro.launch.mesh import make_host_mesh
    from repro.train import step as step_mod
    from repro.train.optimizer import AdamWConfig, init_state
    from repro.train.step import TrainConfig, build_train_step
    cfgj, mix = ctx.config(), ctx.mix()
    cfg = model_config(cfgj, ctx.smoke)
    b, s = mix["batch"], mix["seq"]
    opt = mix["optimizer"]
    mesh = make_host_mesh(devices=len(ctx.devices))
    rows_sh = NamedSharding(mesh, P("data"))
    tc = TrainConfig(adamw=AdamWConfig(
        lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"]))
    fn, in_sh, out_sh, abstract = build_train_step(cfg, mesh, b, s, tc)
    step_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0, 1))
    _, ref_name, flops_per_token = MODELS[cfgj["model"]]
    ref = importlib.import_module(f"refs.{ref_name}")
    balance = (jax.jit(lambda p, t: ref.balance(p, t, cfgj),
                       out_shardings=in_sh[0])
               if hasattr(ref, "balance") else None)
    scale = jax.jit(fan_in, out_shardings=in_sh[0], donate_argnums=(0,))

    def initial():
        """The seeded weights at 1/sqrt(fan-in) (``fan_in``); where the
        reference balances the routers, balanced on step 1's first row
        (program and reference alike)."""
        p = scale(weights.make(abstract[0], weights.seed_key(ctx.seed),
                               shardings=in_sh[0]))
        if balance is not None:
            p = balance(p, batch(ctx.seed, 1, b, s, cfg.vocab)["tokens"][:1])
        return p

    params = initial()
    opt_state = jax.jit(lambda p: init_state(p, tc.adamw),
                        out_shardings=in_sh[1])(params)
    # a program without routing counters records none
    record_routing = getattr(step_mod, "record_routing", None)
    window_pairs = []

    def one(step, keep=None):
        nonlocal params, opt_state
        bt = {k: jnp.asarray(v) for k, v in
              batch(ctx.seed, step, b, s, cfg.vocab).items()}
        with jax.profiler.TraceAnnotation("train.step"):
            params, opt_state, m = step_fn(params, opt_state, bt)
            with jax.profiler.TraceAnnotation("train.loss_readback"):
                loss = float(m["loss"])
        if keep is not None and "moe_pairs" in m:
            keep.append(np.asarray(m["moe_pairs"], np.int64))
            if record_routing is not None:
                record_routing(m)
        return loss

    first = []
    for step in range(1, CHECK_STEPS + 1):
        one(step, first if step == 1 else None)
        if step == 1:
            g_norms = _norms(opt_state["mu"], 1.0 / (1.0 - opt["b1"]))
    p0 = initial()
    change = _diff_norms(params, p0)
    del p0

    step, window_losses, step_s = CHECK_STEPS + 1, [], []
    ctx.start_window()
    while ctx.window_open():
        t0 = time.perf_counter()
        window_losses.append(one(step, window_pairs if ctx.trace else None))
        step_s.append(time.perf_counter() - t0)
        step += 1
    window_s = ctx.end_window()
    del params, opt_state            # the program's state goes first
    dev = (device_times(ctx.trace_dir, len(ctx.devices))
           if ctx.trace and not ctx.smoke else {})

    t0 = time.perf_counter()
    want = _reference(ctx, ref, cfgj, mix, cfg.vocab, initial, in_sh[0],
                      rows_sh, jnp.float32)
    got_pairs = first[0] if first else None
    if ctx.control:
        low = _reference(ctx, ref, cfgj, mix, cfg.vocab, initial, in_sh[0],
                         rows_sh, jnp.dtype(mix["limits"]["control_dtype"]))
        g_norms, change, got_pairs = low["grad"], low["change"], low["pairs"]
    med = float(np.median(np.concatenate(list(want["grad"].values()))))
    keep = {k: v >= mix["limits"]["still_leaf"] * med
            for k, v in want["grad"].items()}
    lim = mix["limits"]
    checks = {
        "grad_norm_gap": {"value": _gap(g_norms, want["grad"], keep),
                          "limit": lim["grad_norm_gap"]},
        "change_norm_gap": {"value": _gap(change, want["change"], keep),
                            "limit": lim["change_norm_gap"]},
    }
    if want["pairs"] is not None:
        gap = (float(np.abs(got_pairs - want["pairs"]).sum()
                     / want["pairs"].sum()) if got_pairs is not None
               else float("inf"))
        checks["pairs_gap"] = {"value": gap, "limit": lim["pairs_gap"]}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    failed = sum(not np.isfinite(x) for x in window_losses)
    tokens = len(window_losses) * b * s
    pattern = cfg.pattern() if hasattr(cfg, "pattern") else ""
    held = getattr(cfg, "experts_held", 0) or cfg.n_experts
    record = {"window_s": window_s, "tokens": tokens,
              "steps": len(window_losses),
              "step_s_median": float(np.median(step_s or [np.nan])),
              "step_s_max": float(np.max(step_s or [np.nan])),
              "check_s": time.perf_counter() - t0,
              "flops_per_token": flops_per_token(cfgj, s),
              "device_s": dev,
              "window_pairs": int(sum(p.sum() for p in window_pairs)),
              "window_moe_calls": int(sum(p.shape[0] for p in window_pairs)),
              "shape": {"batch": b, "seq": s, "d_model": cfg.d_model,
                        "d_ff": cfg.d_ff, "experts_held": held,
                        "n_heads": cfg.n_heads, "kv_heads": cfg.kv_heads,
                        "head_dim": cfg.head_dim,
                        "attn_layers": pattern.count("*")}}
    return {"correct": ok and failed == 0, "attempted": len(window_losses),
            "failed": failed, "record": record, "checks": checks,
            "end_to_end": {"train_tok_s": tokens / window_s}}
