"""Plain float32 reference of an OLMo training step (arXiv:2402.00838,
allenai/OLMo-1B): a decoder of ``n_layers`` blocks, each
``x <- x + attn(LN(x))``, ``x <- x + mlp(LN(x))`` with OLMo's
non-parametric LayerNorm (no gain, no bias, eps 1e-5); multi-head causal
attention (``n_heads`` of ``head_dim``, scale 1/sqrt(head dim)) with RoPE
(theta 1e4) on q and k; a SwiGLU MLP, silu(x W_g) * (x W_i) W_o; a final
LayerNorm and an untied head.

Imports nothing of the program; reads the configuration file's keys and
the parameter tree that ``weights.make`` seeds (layers stacked under
``layers``).  RoPE rotates each head's dimensions in adjacent pairs
(2i, 2i+1) at frequency theta^(-2i/d); OLMo's code rotates the two halves
(i, i + d/2) instead, which is the same map up to a fixed permutation of
each head's dimensions: seeded weights cannot tell them apart.

The loss is the mean next-token cross-entropy; ``dtype`` rounds every
matmul's inputs to that type first, accumulating in float32 (the control's
lower precision).  Under ``jit`` with sharded parameters and rows the same
code runs sharded over a data mesh.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from refs.mamba2 import _mm, adamw

F32 = jnp.float32
__all__ = ["adamw", "loss"]


def layernorm(x, eps: float = 1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rope(x, theta: float = 1e4):
    """x (B,L,H,D) at positions 0..L-1."""
    ln, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(ln, dtype=F32)[:, None, None] * freqs     # (L,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def block(lp: Dict, x, cfg: Dict, dtype=F32):
    ln, hd = x.shape[1], cfg["head_dim"]
    a = lp["attn"]
    xn = layernorm(x)
    q = rope(_mm("bld,dhk->blhk", xn, a["wq"], dtype=dtype))
    k = rope(_mm("bld,dhk->blhk", xn, a["wk"], dtype=dtype))
    v = _mm("bld,dhk->blhk", xn, a["wv"], dtype=dtype)
    sc = _mm("bqhd,bthd->bhqt", q, k, dtype=dtype) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((ln, ln), bool))
    w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    o = _mm("bhqt,bthd->bqhd", w, v, dtype=dtype)
    x = x + _mm("blhk,hkd->bld", o, a["wo"], dtype=dtype)
    f = lp["ffn"]
    xn = layernorm(x)
    h = (jax.nn.silu(_mm("bld,df->blf", xn, f["wg"], dtype=dtype))
         * _mm("bld,df->blf", xn, f["wi"], dtype=dtype))
    return x + _mm("blf,fd->bld", h, f["wo"], dtype=dtype)


def loss(params: Dict, tokens, targets, cfg: Dict, dtype=F32):
    """(mean next-token cross-entropy, {})."""
    x = params["embed"][tokens].astype(F32)

    def body(x, lp):
        return block(lp, x, cfg, dtype), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    logits = _mm("bld,dv->blv", layernorm(x), params["unembed"], dtype=dtype)
    logz = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - gold), {}
