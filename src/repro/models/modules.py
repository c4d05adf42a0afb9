"""Core model building blocks — functional, pytree-param style.

Parameters are nested dicts of arrays.  ``abstract=True`` builds
``jax.ShapeDtypeStruct`` trees instead of allocating (the multi-pod
dry-run lowers against these).  Every parameter carries *logical axis*
names in a parallel tree, consumed by ``repro.parallel.sharding``.

Attention/FFN math uses plain jnp (XLA-fusable and SPMD-partitionable),
except where a Pallas kernel of ``repro.kernels`` takes its place: causal
self-attention on TPUs (splash) and the experts (``models.moe``), each run
per device shard under a mesh.  The kernels are checked against these
references in interpret mode on the CPU.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels.train_attention import block_for, causal_attention

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
    return rmsnorm(x, gamma, cfg.norm_eps)


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


def _kernel_fits(s: int) -> bool:
    """Causal self-attention runs through the splash kernel (forward and
    backward, no S x S tensor) on TPUs where its blocks tile the sequence;
    elsewhere over the S x S scores."""
    from ..parallel.ctx import platform
    return platform() == "tpu" and block_for(s) > 0


def _kernel_attention(q, k, v):
    """``causal_attention`` on each device's shard of the enclosing mesh:
    its batch rows, and its heads where query and KV heads shard alike."""
    from ..parallel import ctx
    if ctx.sharded_mesh() is None:
        return causal_attention(q, k, v)
    qs = ctx.spec_of(q, ("act_batch", None, "heads", None))
    ks = ctx.spec_of(k, ("act_batch", None, "kv_heads", None))
    b_ax, h_ax = ctx.entry(qs, 0), ctx.entry(qs, 2)
    if h_ax != ctx.entry(ks, 2):
        h_ax = None
    qs, ks = P(b_ax, None, h_ax), P(ctx.entry(ks, 0), None, h_ax)
    return ctx.shard_local(causal_attention, in_specs=(qs, ks, ks),
                           out_specs=qs)(q, k, v)


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
    if kv_override is None and causal and _kernel_fits(s):
        with jax.named_scope("attn"):
            ctx = _kernel_attention(q, k, v)
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


# --------------------------------------------------------------------------
# FFN: dense (SwiGLU / GELU); experts in ``models.moe``
# --------------------------------------------------------------------------

def ffn_specs(cfg) -> Params:
    if cfg.n_experts > 1:
        from . import moe
        return moe.specs(cfg)
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


def ffn(p: Params, x, cfg):
    if cfg.n_experts > 1:
        from . import moe
        return moe.moe(p, x, cfg)[0]
    return dense_ffn(p, x, cfg)
