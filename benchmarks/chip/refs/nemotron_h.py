"""Plain float32 reference of a Nemotron-H training step (``model_type``
nemotron_h, as NVIDIA-Nemotron-3-Nano-30B-A3B), on one chip's share.

Imports nothing of the program.  It reads the configuration file's own
keys (the published ``config.json`` names, with the cut applied) and the
parameter tree that ``weights.make`` seeds.  Every layer is
``x <- x + mixer(RMSNorm(x))``, eps ``layer_norm_epsilon``; one mixer per
layer, by the characters of ``hybrid_override_pattern``:

* ``M`` (Mamba-2): ``in_proj`` to [z | xBC | dt], d_inner = heads x head
  dim; xBC <- silu(causal depthwise conv + bias); split into x, B and C
  (``n_groups`` x state each, head h reading group h // (heads / groups));
  dt <- softplus(dt + dt_bias); A = -exp(A_log); the SSD scan in the
  chunked form of arXiv:2405.21060's minimal listing; y += D x;
  y <- RMSNorm(y silu(z)) over groups of d_inner / n_groups channels, times
  its gain; ``out_proj``.
* ``E`` (experts): s = sigmoid(x W_r) over all experts; the picks are the
  top ``num_experts_per_tok`` of s + b (b selects and weighs nothing);
  weights s at the picks, over their sum, times ``routed_scaling_factor``;
  out = sum_k w_k W_down,k relu(W_up,k x)^2 + W_down,sh relu(W_up,sh x)^2.
  The held experts are computed densely for every token and masked by the
  routing weights, so a pair routed elsewhere adds nothing.  No gradient
  trains b: after each step ``rebias`` moves each expert's b by
  ``training.router_bias_update_rate`` against its load, up where it got
  fewer pairs than the mean over experts and down where it got more.
* ``*`` (attention): q, k and v projections (GQA), causal softmax
  attention with scale 1/sqrt(head dim) and no positional encoding,
  ``o_proj``; computed in blocks of queries, each over every key.

A final RMSNorm and the untied head follow; the loss is the mean
next-token cross-entropy over the vocabulary slice.  ``dtype`` rounds every
matmul's inputs to that type first, accumulating in float32: ``float32``
at highest precision is the reference, a lower type the control.
``balance`` sets the seeded weights' selection biases as trained ones sit:
each expert layer's picks spread evenly over its experts.  ``loss`` returns
the routing counts: pairs per held expert (``moe_pairs``) and per expert
(``moe_load``), per expert layer.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from refs.mamba2 import _mm, adamw, rmsnorm, segsum

F32 = jnp.float32
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
Q_BLOCK = 512
__all__ = ["adamw", "balance", "biases", "loss", "rebias"]


def ssd(x, a, b, c, chunk, dtype=F32):
    """x (B,L,H,P) already times dt; a (B,L,H) = A dt; b, c (B,L,H,N),
    each head's own.  Returns y (B,L,H,P)."""
    bs, ln, h, p = x.shape
    nc = ln // chunk
    x = x.reshape(bs, nc, chunk, h, p)
    b = b.reshape(bs, nc, chunk, h, -1)
    c = c.reshape(bs, nc, chunk, h, -1)
    a = jnp.moveaxis(a.reshape(bs, nc, chunk, h), -1, 1)   # (B,H,C,l)
    a_cs = jnp.cumsum(a, -1)
    decay = jnp.exp(segsum(a))                               # (B,H,C,l,s)
    y_diag = _mm("bclhn,bcshn,bhcls,bcshp->bclhp", c, b, decay, x,
                 dtype=dtype)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)            # (B,H,C,l)
    states = _mm("bclhn,bhcl,bclhp->bchpn", b, decay_states, x,
                 dtype=dtype)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = _mm("bhzc,bchpn->bzhpn", decay_chunk, states,
                 dtype=dtype)[:, :-1]
    y_off = _mm("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs),
                dtype=dtype)
    return (y_diag + y_off).reshape(bs, ln, h, p)


def mamba(lp: Dict, x, cfg: Dict, dtype=F32):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    di, k = h * p, cfg["conv_kernel"]
    proj = _mm("bld,de->ble", x, lp["w_in"], dtype=dtype)
    z, xbc = proj[..., :di], proj[..., di:2 * di + 2 * g * n]
    dt = proj[..., 2 * di + 2 * g * n:]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + xbc.shape[1]] * lp["conv_w"][i]
               for i in range(k))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    lead = xbc.shape[:2]
    xh = xbc[..., :di].reshape(lead + (h, p))
    heads = lambda m: jnp.repeat(m.reshape(lead + (g, n)), h // g, 2)  # noqa
    bm, cm = heads(xbc[..., di:di + g * n]), heads(xbc[..., di + g * n:])
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # (B,L,H)
    a = -jnp.exp(lp["a_log"])
    y = ssd(xh * dt[..., None], a * dt, bm, cm, cfg["chunk_size"], dtype)
    y = (y + lp["d_skip"][:, None] * xh).reshape(lead + (di,))
    v = (y * jax.nn.silu(z)).reshape(lead + (g, di // g))
    v = rmsnorm(v, 1.0, cfg["layer_norm_epsilon"]).reshape(lead + (di,))
    return _mm("ble,ed->bld", v * lp["out_norm"], lp["w_out"], dtype=dtype)


def experts(lp: Dict, x, cfg: Dict, dtype=F32) -> Tuple[jax.Array,
                                                         jax.Array]:
    """(output (B,L,D), {"moe_pairs": pairs routed to each held expert,
    "moe_load": pairs routed to each expert})."""
    bs, ln, d = x.shape
    xt = x.reshape(bs * ln, d)
    s = jax.nn.sigmoid(_mm("nd,de->ne", xt, lp["router"], dtype=dtype))
    _, idx = jax.lax.top_k(s + lp["router_bias"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    held = lp["w_up"].shape[1]
    hit = idx[..., None] == jnp.arange(held)                 # (N,k,E)
    gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), 1)     # (N,E)
    up = jnp.square(jax.nn.relu(_mm("nd,def->nef", xt, lp["w_up"],
                                    dtype=dtype)))
    y = _mm("nef,fed,ne->nd", up, lp["w_down"], gate, dtype=dtype)
    sh = jnp.square(jax.nn.relu(_mm("nd,df->nf", xt, lp["shared_up"],
                                    dtype=dtype)))
    y = y + _mm("nf,fd->nd", sh, lp["shared_down"], dtype=dtype)
    load = jnp.sum(idx[..., None] == jnp.arange(s.shape[-1]), (0, 1))
    return y.reshape(bs, ln, d), {"moe_pairs": jnp.sum(hit, (0, 1)),
                                  "moe_load": load}


def attention(lp: Dict, x, cfg: Dict, dtype=F32):
    bs, ln, _ = x.shape
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _mm("bld,dhk->blhk", x, lp["wq"], dtype=dtype)
    k = _mm("bld,dhk->blhk", x, lp["wk"], dtype=dtype)
    v = _mm("bld,dhk->blhk", x, lp["wv"], dtype=dtype)
    blk = min(Q_BLOCK, ln)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 1)
        qi = qi.reshape(bs, blk, hk, hq // hk, hd)
        sc = _mm("bqkgd,btkd->bkgqt", qi, k, dtype=dtype) / math.sqrt(hd)
        seen = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(ln)
        w = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return _mm("bkgqt,btkd->bqkgd", w, v,
                   dtype=dtype).reshape(bs, blk, hq, hd)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(ln // blk))
    out = jnp.moveaxis(out, 0, 1).reshape(bs, ln, hq, hd)
    return _mm("blhk,hkd->bld", out, lp["wo"], dtype=dtype)


def layer_names(cfg: Dict):
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return [(f"l{i}_{KINDS[c]}", c) for i, c in enumerate(pattern)]


def layer(lp: Dict, x, c: str, cfg: Dict, dtype=F32):
    """One layer of kind ``c``: (x, routing counts of ``experts`` or None)."""
    xn = rmsnorm(x, lp["norm"], cfg["layer_norm_epsilon"])
    if c == "M":
        return x + mamba(lp, xn, cfg, dtype), None
    if c == "E":
        y, n = experts(lp["moe"], xn, cfg, dtype)
        return x + y, n
    return x + attention(lp["attn"], xn, cfg, dtype), None


def forward(params: Dict, tokens, cfg: Dict, dtype=F32):
    """(logits (B,L,V), the expert layers' routing counts, stacked:
    ``moe_pairs`` (n_E, E_held) and ``moe_load`` (n_E, experts))."""
    x = params["embed"][tokens].astype(F32)
    counts = []
    for name, c in layer_names(cfg):
        x, n = jax.checkpoint(functools.partial(layer, c=c, cfg=cfg,
                                                dtype=dtype))(
            params["blocks"][name], x)
        if n is not None:
            counts.append(n)
    x = rmsnorm(x, params["final_norm"], cfg["layer_norm_epsilon"])
    return (_mm("bld,dv->blv", x, params["unembed"], dtype=dtype),
            {k: jnp.stack([n[k] for n in counts]) for k in counts[0]})


def _spread(s, k: int, iters: int):
    """A bias b such that the top ``k`` of ``s + b`` (N, E) give every
    expert close to N k / E rows: sign steps against each expert's excess
    load, shrinking geometrically."""
    n, e = s.shape

    def step(i, b):
        _, idx = jax.lax.top_k(s + b, k)
        load = jnp.zeros((e,), F32).at[idx.reshape(-1)].add(1.0)
        return b - 0.05 * 0.97 ** i * jnp.sign(load - n * k / e)
    return jax.lax.fori_loop(0, iters, step, jnp.zeros((e,), F32))


def balance(params: Dict, tokens, cfg: Dict, iters: int = 200) -> Dict:
    """The weights with every expert layer's selection bias set, layer
    after layer, so that its picks spread evenly over all experts on
    ``tokens``: the state that the published model's trained
    ``e_score_correction_bias`` keeps (auxiliary-loss-free balancing,
    arXiv:2412.19437 section 2.1.2).  Seeded weights alone send most
    tokens to a few experts, a different few for every seed."""
    blocks = dict(params["blocks"])
    x = params["embed"][tokens].astype(F32)
    for name, c in layer_names(cfg):
        lp = blocks[name]
        if c == "E":
            xn = rmsnorm(x, lp["norm"], cfg["layer_norm_epsilon"])
            s = jax.nn.sigmoid(_mm("bld,de->ble", xn, lp["moe"]["router"]))
            b = _spread(s.reshape(-1, s.shape[-1]),
                        cfg["num_experts_per_tok"], iters)
            lp = blocks[name] = dict(lp, moe=dict(lp["moe"], router_bias=b))
        x, _ = layer(lp, x, c, cfg)
    return dict(params, blocks=blocks)


def biases(params: Dict, cfg: Dict) -> Dict:
    """Each expert layer's selection bias, copied to the host."""
    return {name: np.asarray(params["blocks"][name]["moe"]["router_bias"])
            for name, c in layer_names(cfg) if c == "E"}


def rebias(params: Dict, before: Dict, load, cfg: Dict) -> Dict:
    """``params`` with each expert layer's selection bias set to its value
    ``before`` the step plus ``training.router_bias_update_rate`` times
    the sign of the mean load less the expert's (``load`` (n_E, experts):
    the step's pairs per expert)."""
    rate = cfg["training"]["router_bias_update_rate"]
    blocks = dict(params["blocks"])
    for name, n in zip(before, np.asarray(load, np.float64)):
        b = before[name] + rate * np.sign(n.mean() - n)
        lp = blocks[name]
        blocks[name] = dict(lp, moe=dict(lp["moe"], router_bias=jnp.asarray(
            b, lp["moe"]["router_bias"].dtype)))
    return dict(params, blocks=blocks)


def loss(params: Dict, tokens, targets, cfg: Dict, dtype=F32):
    """(mean next-token cross-entropy, the routing counts of
    ``forward``)."""
    logits, counts = forward(params, tokens, cfg, dtype)
    logz = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - gold), counts
