"""The expert layer: routes every token over all ``n_experts``, computes the
part of the result that the ``experts_held`` experts held here give, and
drops no token.

Under expert parallelism each chip holds a share of the experts; the
router keeps its published width and its ``top_k``, and the layer is told
which experts it holds (``first_expert`` and ``cfg.experts_held``).  The
token-expert pairs are sorted by expert (held experts first, in order,
then every other pair) by counting, not by a sort; the held experts' rows
run through one grouped matmul per projection (megablox ``gmm``), which
computes only the held groups' tiles and zeroes every other row.  Nothing
has a capacity: the buffer holds all ``tokens x top_k`` pairs.  A shared
expert, where the configuration has one, runs on every token.  Under a
mesh of several devices the experts run on each device's shard of them
(or of their width), as the sharding rules lay the weights out.

Routers: ``softmax`` (granite, grok, jamba: the top-k of the softmax,
renormalised) and ``sigmoid`` (nemotron_h: sigmoid scores in float32, the
top-k chosen on the scores plus a selection bias that weighs nothing,
renormalised, times ``routed_scale``).  No gradient trains the selection
bias: after each step :func:`rebias` moves it against each expert's load
(auxiliary-loss-free balancing, arXiv:2412.19437 section 2.1.2), from the
pairs the layer counts for every expert.  Experts are SwiGLU or relu².
Expert leaves keep their input width first, (d, E, ff) and (ff, E, d).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.ops import gmm
from ..parallel import ctx
from .config import ModelConfig
from .modules import ParamSpec

Params = Dict[str, Any]


def specs(cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held_experts
    p: Params = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", "expert")),
        "w_up": ParamSpec((d, e, f), ("embed", "expert", "mlp")),
        "w_down": ParamSpec((f, e, d), ("mlp", "expert", "embed")),
    }
    if cfg.ffn_act == "swiglu":
        p["w_gate"] = ParamSpec((d, e, f), ("embed", "expert", "mlp"))
    if cfg.router == "sigmoid":
        p["router_bias"] = ParamSpec((cfg.n_experts,), ("expert",))
    if cfg.shared_ff:
        p["shared_up"] = ParamSpec((d, cfg.shared_ff), ("embed", "mlp"))
        p["shared_down"] = ParamSpec((cfg.shared_ff, d), ("mlp", "embed"))
    return p


def route(p: Params, xt, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """(expert ids (N, k), weights (N, k) float32) of every token."""
    logits = jnp.dot(xt.astype(jnp.float32),
                     p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        choice = probs + p["router_bias"].astype(jnp.float32)
    else:
        probs = choice = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg.routed_scale


def _rows(m: int) -> Tuple[int, int]:
    """(row tile, rows padded to it) of a buffer of ``m`` pairs."""
    tm = min(512, -(-m // 128) * 128)
    return tm, -(-m // tm) * tm


def _sort_pairs(idx, held: int, first_expert: int, rows: int):
    """Each pair's row in the expert-sorted buffer, the pair at each row
    (``n_pairs`` for the padding rows) and the rows per group: the held
    experts in order, then one group of every other pair and the padding."""
    k = idx.shape[-1]
    local = idx.reshape(-1) - first_expert
    group = jnp.where((local >= 0) & (local < held), local, held)
    onehot = (group[:, None] == jnp.arange(held + 1)).astype(jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    sizes = jnp.sum(onehot, axis=0)
    starts = jnp.cumsum(sizes) - sizes
    pos = starts[group] + jnp.take_along_axis(rank, group[:, None], 1)[:, 0]
    n_pairs = idx.shape[0] * k
    pair_at = jnp.full((rows,), n_pairs, jnp.int32).at[pos].set(
        jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
    sizes = sizes.at[held].add(rows - n_pairs)
    return pos, pair_at, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, pair_at, pos, k: int):
    """Row r of the sorted buffer is the token of pair ``pair_at[r]``.  The
    backward gathers each token's k rows back (no scatter)."""
    return xt[jnp.minimum(pair_at // k, xt.shape[0] - 1)]


def _dispatch_fwd(xt, pair_at, pos, k):
    return _dispatch(xt, pair_at, pos, k), pos


def _dispatch_bwd(k, pos, g):
    return g[pos].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect(y, pos, pair_at):
    """Each pair's output row, in pair order.  The backward gathers the
    rows back into sorted order, zero for the padding rows."""
    return y[pos]


def _collect_fwd(y, pos, pair_at):
    return y[pos], pair_at


def _collect_bwd(pair_at, g):
    g = jnp.concatenate([g, jnp.zeros((1, g.shape[-1]), g.dtype)])
    return g[pair_at], None, None


_collect.defvjp(_collect_fwd, _collect_bwd)


def _act(cfg: ModelConfig, up, gate=None):
    if cfg.ffn_act == "swiglu":
        return jax.nn.silu(gate) * up
    return jnp.square(jax.nn.relu(up))


def _experts(xt, idx, w, w_up, w_gate, w_down, first_expert, cfg):
    """The part that the experts of ``w_up`` (d, E, f), numbered from
    ``first_expert``, give each token (N, D) float32; pairs routed to
    each of them (E,)."""
    n, k = idx.shape
    held, cd = w_up.shape[1], cfg.compute_dtype
    with jax.named_scope("moe.dispatch"):
        _, rows = _rows(n * k)
        pos, pair_at, sizes = _sort_pairs(idx, held, first_expert, rows)
        xs = _dispatch(xt, pair_at, pos, k)
    with jax.named_scope("moe.gmm"):
        def grouped(a, wt):           # (d, E, f) leaf → (E, d, f) operand
            return gmm(a, jnp.swapaxes(wt.astype(cd), 0, 1), sizes)
        gate = grouped(xs, w_gate) if w_gate is not None else None
        ys = grouped(_act(cfg, grouped(xs, w_up), gate), w_down)
    with jax.named_scope("moe.combine"):
        ypair = _collect(ys, pos, pair_at).reshape(n, k, -1)
        out = jnp.einsum("nk,nkd->nd", w.astype(cd), ypair,
                         preferred_element_type=jnp.float32)
    return out, sizes[:held]


def _experts_on_mesh(xt, idx, w, w_up, w_gate, w_down, first_expert, cfg):
    """``_experts`` on each device of the enclosing mesh (the grouped
    matmul is a Pallas call, which the partitioner cannot split).  Tokens
    stay on their batch shard.  Where the rules shard the experts
    (``expert``), each device routes its tokens over every expert and
    computes those it holds; where they shard the expert width (``mlp``),
    each computes its columns of every expert.  The parts add up over
    those mesh axes (one all-reduce of the output); the weights are
    gathered over the others (``embed``'s FSDP axis) as any matmul's are."""
    tok = ctx.spec_of(xt, ("act_batch", None))
    wspec = ctx.spec_of(w_up, (None, "expert", "mlp"))
    e_ax, f_ax = ctx.entry(wspec, 1), ctx.entry(wspec, 2)
    t_ax = ctx.entry(tok, 0)
    parts = tuple(a for a in (e_ax, f_ax) if a is not None)
    up_spec, down_spec = P(None, e_ax, f_ax), P(f_ax, e_ax, None)

    def local(xt, idx, w, w_up, w_gate, w_down):
        first = first_expert
        if e_ax is not None:
            first = first + jax.lax.axis_index(e_ax) * w_up.shape[1]
        out, pairs = _experts(xt, idx, w, w_up, w_gate, w_down, first, cfg)
        out = out.astype(cfg.compute_dtype)
        if parts:
            out = jax.lax.psum(out, parts)
        if t_ax is not None:
            pairs = jax.lax.psum(pairs, t_ax)
        return out, pairs

    return ctx.shard_local(
        local,
        in_specs=(tok, tok, tok, up_spec,
                  None if w_gate is None else up_spec, down_spec),
        out_specs=(tok, P(e_ax)))(xt, idx, w, w_up, w_gate, w_down)


# step of the selection bias after each training step: Megatron-core's
# moe_router_bias_update_rate default, DeepSeek-V3's gamma
BIAS_RATE = 1e-3


def rebias(bias, load):
    """The selection bias after a step: each expert's moved by
    ``BIAS_RATE`` against its load, ``load`` (n_experts,) pairs over their
    mean (up where an expert got fewer, down where more)."""
    load = load.astype(jnp.float32)
    return (bias.astype(jnp.float32) + BIAS_RATE
            * jnp.sign(jnp.mean(load) - load)).astype(bias.dtype)


def moe(p: Params, x, cfg: ModelConfig, first_expert: int = 0):
    """x (B, S, D) → (the held experts' part of the layer's output plus the
    shared expert's, (B, S, D); {"moe_pairs": pairs routed to each held
    expert (E_held,)}, with "moe_load": pairs routed to every expert
    (n_experts,) where the router has a selection bias)."""
    b, s, d = x.shape
    cd = cfg.compute_dtype
    xt = x.reshape(b * s, d).astype(cd)
    with jax.named_scope("moe.route"):
        idx, w = route(p, xt, cfg)
        stats = {}
        if cfg.router == "sigmoid":
            stats["moe_load"] = jnp.sum(
                idx[..., None] == jnp.arange(cfg.n_experts), axis=(0, 1),
                dtype=jnp.int32)
    experts = (_experts_on_mesh if ctx.sharded_mesh() is not None
               else _experts)
    out, pairs = experts(xt, idx, w, p["w_up"], p.get("w_gate"),
                         p["w_down"], first_expert, cfg)
    if cfg.shared_ff:
        with jax.named_scope("moe.shared"):
            h = _act(cfg, xt @ p["shared_up"].astype(cd))
            out = out + (h @ p["shared_down"].astype(cd)).astype(jnp.float32)
    return out.reshape(b, s, d).astype(x.dtype), dict(stats, moe_pairs=pairs)
