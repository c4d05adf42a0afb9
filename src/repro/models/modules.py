"""Core model building blocks — functional, pytree-param style.

Parameters are nested dicts of arrays.  ``abstract=True`` builds
``jax.ShapeDtypeStruct`` trees instead of allocating (the multi-pod
dry-run lowers against these).  Every parameter carries *logical axis*
names in a parallel tree, consumed by ``repro.parallel.sharding``.

Attention/FFN math uses plain jnp (XLA-fusable and SPMD-partitionable);
the Pallas TPU kernels in ``repro.kernels`` implement the same contracts
for the perf-critical paths and are validated against these references in
interpret mode (CPU container — see DESIGN.md §6).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# Parameter declaration
# --------------------------------------------------------------------------

class ParamSpec:
    """Declares one parameter: shape + logical axes + init scale."""

    def __init__(self, shape, axes, scale: float = 1.0, dtype=jnp.float32):
        assert len(shape) == len(axes), (shape, axes)
        self.shape = tuple(int(s) for s in shape)
        self.axes = tuple(axes)
        self.scale = scale
        self.dtype = dtype


def materialize(tree, rng: Optional[jax.Array], abstract: bool,
                param_dtype=jnp.float32):
    """Turn a ParamSpec tree into arrays (or ShapeDtypeStructs)."""
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    out = []
    if rng is not None:
        keys = jax.random.split(rng, len(leaves))
    for i, spec in enumerate(leaves):
        if abstract:
            out.append(jax.ShapeDtypeStruct(spec.shape, param_dtype))
        else:
            # stacked layers share one spec: the fan-in is the first
            # dimension after the stacking axes
            dims = [n for n, ax in zip(spec.shape, spec.axes)
                    if ax not in ("layers", "layers2")]
            fan_in = dims[0] if dims else 1
            std = spec.scale / math.sqrt(max(1, fan_in))
            out.append(std * jax.random.normal(keys[i], spec.shape,
                                               param_dtype))
    return jax.tree.unflatten(treedef, out)


def axes_tree(tree):
    """Parallel tree of logical-axes tuples."""
    return jax.tree.map(lambda s: s.axes, tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------

def rmsnorm(x, gamma=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if gamma is not None:
        y = y * gamma
    return y.astype(x.dtype)


def layernorm_nonparametric(x, eps: float = 1e-5):
    """OLMo-style non-parametric LayerNorm (no scale/bias)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def norm(x, gamma, cfg) -> jax.Array:
    if cfg.ln_kind == "nonparametric":
        return layernorm_nonparametric(x)
    return rmsnorm(x, gamma)


# --------------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D); positions: (..., S) int."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (...,S,D/2)
    ang = ang[..., None, :]                                  # (...,S,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_mrope(x, positions3, sections=(16, 24, 24), theta: float = 1e6):
    """Qwen2-VL multimodal RoPE: head_dim/2 rotary freqs split into
    (temporal, height, width) sections, each driven by its own position
    stream.  positions3: (..., S, 3)."""
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = jnp.asarray(rope_freqs(d, theta), dtype=jnp.float32)  # (d/2,)
    sec_id = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.asarray(sec_id)[None, None, :].astype(jnp.int32)
        * jnp.ones(positions3.shape[:-1] + (d // 2,), jnp.int32),
        axis=-1)                                             # (...,S,d/2)
    ang = pos * freqs
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA) — reference math used by train/prefill and the dry-run
# --------------------------------------------------------------------------

def attention_specs(cfg) -> Params:
    hd = cfg.head_dim
    return {
        "wq": ParamSpec((cfg.d_model, cfg.n_heads, hd),
                        ("embed", "heads", "head_dim")),
        "wk": ParamSpec((cfg.d_model, cfg.kv_heads, hd),
                        ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((cfg.d_model, cfg.kv_heads, hd),
                        ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((cfg.n_heads, hd, cfg.d_model),
                        ("heads", "head_dim", "embed")),
    }


def _rope_qk(q, k, positions, cfg):
    if cfg.rope == "mrope":
        return (apply_mrope(q, positions, cfg.mrope_sections),
                apply_mrope(k, positions, cfg.mrope_sections))
    if cfg.rope == "rope":
        return (apply_rope(q, positions, cfg.rope_theta),
                apply_rope(k, positions, cfg.rope_theta))
    return q, k


def gqa_attention(p: Params, x, positions, cfg, causal: bool = True,
                  kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                  kv_positions: Optional[jax.Array] = None):
    """x: (B, S, D).  Returns (out, (k, v)) — k/v pre-RoPE'd cache lines.

    With ``kv_override`` (decode), x provides queries only and attention
    runs against the supplied cache (B, S_kv, kvH, hd).
    """
    b, s, _ = x.shape
    p = jax.tree.map(lambda a: a.astype(cfg.compute_dtype), p)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).astype(cfg.compute_dtype)
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"]).astype(cfg.compute_dtype)
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"]).astype(cfg.compute_dtype)
        q, k = _rope_qk(q, k, positions, cfg)
        kv_pos = positions
    else:
        k, v = kv_override
        k = k.astype(cfg.compute_dtype)
        v = v.astype(cfg.compute_dtype)
        q, _ = _rope_qk(q, q, positions, cfg)   # rope on q only
        kv_pos = kv_positions
    groups = cfg.n_heads // cfg.kv_heads
    qg = q.reshape(b, s, cfg.kv_heads, groups, cfg.head_dim)
    if cfg.attn_impl == "chunked" and kv_override is None and causal:
        ctx = _chunked_causal_attention(qg, k, v, cfg)
    else:
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) \
            / math.sqrt(cfg.head_dim)
        if causal and kv_override is None:
            mask = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(mask[None, None, None], scores, -1e30)
        elif kv_override is not None and kv_pos is not None:
            # decode: mask cache slots beyond each sequence's length
            valid = kv_pos[:, None, None, None, :] >= 0
            scores = jnp.where(valid, scores, -1e30)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1) \
            .astype(cfg.compute_dtype)
        ctx = jnp.einsum("bkgst,btkd->bskgd", w, v)
    ctx = ctx.reshape(b, s, cfg.n_heads, cfg.head_dim)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    return out, (k, v)


def _chunked_causal_attention(qg, k, v, cfg):
    """Streaming-softmax attention over KV chunks (flash contract in jnp):
    never materializes the (S, S) score matrix — the memory-roofline
    optimization for long prefill (§Perf cell B).  On TPU hardware the
    Pallas flash kernel implements the identical math."""
    b, s, kvh, g, d = qg.shape
    ck = min(cfg.attn_chunk, s)
    n_chunks = s // ck
    scale = 1.0 / math.sqrt(d)
    kc = k.reshape(b, n_chunks, ck, kvh, d)
    vc = v.reshape(b, n_chunks, ck, kvh, d)
    q_pos = jnp.arange(s)

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, j = inp
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, kj) * scale
        kv_pos = j * ck + jnp.arange(ck)
        mask = q_pos[:, None] >= kv_pos[None, :]
        sc = jnp.where(mask[None, None, None], sc.astype(jnp.float32),
                       -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(cfg.compute_dtype),
            vj).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(n_chunks)))
    ctx = (acc / jnp.maximum(l, 1e-30)[..., None]) \
        .astype(cfg.compute_dtype)
    return jnp.moveaxis(ctx, 3, 1).reshape(b, s, kvh, g, d)


# --------------------------------------------------------------------------
# FFN: dense (SwiGLU / GELU) and Mixture-of-Experts
# --------------------------------------------------------------------------

def ffn_specs(cfg) -> Params:
    if cfg.n_experts > 1:
        e = cfg.n_experts
        return {
            "router": ParamSpec((cfg.d_model, e), ("embed", "expert")),
            "wi": ParamSpec((e, cfg.d_model, cfg.d_ff),
                            ("expert", "embed", "mlp")),
            "wg": ParamSpec((e, cfg.d_model, cfg.d_ff),
                            ("expert", "embed", "mlp")),
            "wo": ParamSpec((e, cfg.d_ff, cfg.d_model),
                            ("expert", "mlp", "embed")),
        }
    if cfg.ffn_act == "swiglu":
        return {
            "wi": ParamSpec((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
            "wg": ParamSpec((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
            "wo": ParamSpec((cfg.d_ff, cfg.d_model), ("mlp", "embed")),
        }
    return {
        "wi": ParamSpec((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
        "wo": ParamSpec((cfg.d_ff, cfg.d_model), ("mlp", "embed")),
    }


def dense_ffn(p: Params, x, cfg):
    p = jax.tree.map(lambda a: a.astype(cfg.compute_dtype), p)
    if cfg.ffn_act == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]


def moe_ffn(p: Params, x, cfg):
    """Top-k MoE with capacity-based sort dispatch (grouped GEMM).

    Tokens are flattened, routed, sorted by expert, packed into an
    (E, C, D) buffer (overflow dropped — capacity factor 1.25), processed
    with per-expert einsums (EP-shardable on the 'expert' axis; the
    pack/unpack scatter induces the expected all-to-all), and combined
    with router weights.
    """
    b, s, d = x.shape
    p = jax.tree.map(lambda a: a.astype(cfg.compute_dtype), p)
    n = b * s
    xt = x.reshape(n, d).astype(cfg.compute_dtype)
    e, k = cfg.n_experts, cfg.top_k
    logits = (xt @ p["router"]).astype(jnp.float32)          # (N, E)
    gates, idx = jax.lax.top_k(logits, k)                    # (N, k)
    gates = jax.nn.softmax(gates, axis=-1).astype(cfg.compute_dtype)
    cap = int(math.ceil(n * k / e * cfg.capacity_factor))
    cap = max(cap, 8)

    flat_e = idx.reshape(-1)                                 # (N*k,)
    order = jnp.argsort(flat_e)                              # stable
    sorted_e = flat_e[order]
    # rank of each pair within its expert
    starts = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank = jnp.arange(n * k) - starts[sorted_e]
    keep = rank < cap
    slot = jnp.where(keep, sorted_e * cap + rank, e * cap)   # overflow bin
    tok = order // k                                         # source token

    from ..parallel.ctx import constrain
    buf = jnp.zeros((e * cap + 1, d), cfg.compute_dtype)
    buf = buf.at[slot].add(xt[tok].astype(cfg.compute_dtype))
    # expert-sharded buffer: the scatter above lowers to the expected
    # token all-to-all under expert parallelism
    buf = constrain(buf[:-1].reshape(e, cap, d), ("expert", None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wo"])           # (E, C, D)

    flat_out = jnp.concatenate(
        [out_e.reshape(e * cap, d),
         jnp.zeros((1, d), out_e.dtype)], axis=0)
    pair_out = flat_out[slot]                                # (N*k, D)
    pair_gate = gates.reshape(-1)[order]
    y = jnp.zeros((n, d), cfg.compute_dtype)
    y = y.at[tok].add(pair_out * pair_gate[:, None])
    return y.reshape(b, s, d).astype(x.dtype)


def ffn(p: Params, x, cfg):
    if cfg.n_experts > 1:
        return moe_ffn(p, x, cfg)
    return dense_ffn(p, x, cfg)
