"""Plain float32 reference of a Mamba-2 language model's training step.

Imports nothing of the program.  The block follows arXiv:2405.21060:
RMSNorm, one input projection split into the gate z, the conv input
(x, B, C) and dt; a depthwise causal conv of width ``d_conv`` with SiLU;
dt through a softplus with its bias; A = -exp(a_log); the SSD scan in the
paper's chunked form (its minimal listing: diagonal blocks through the
segment-sum decay, chunk states, the recurrence between chunks, the
states' read-out), plus the D skip; the gated RMSNorm of y·silu(z); the
output projection and the residual.  One B and C shared by all heads
(one group).  The loss is the mean next-token cross-entropy; the update
is AdamW with bias correction and decoupled weight decay on every leaf.

``dtype`` rounds every matmul's inputs (and the SSD's) to that type first,
accumulating in float32: ``float32`` at highest precision is the reference,
a lower type the control.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _mm(spec, *xs, dtype=F32):
    return jnp.einsum(spec, *(x.astype(dtype) for x in xs),
                      preferred_element_type=F32)


def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def segsum(x):
    """x (..., T) → (..., T, T): sum of x over (s, t] below the diagonal,
    −inf above it."""
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (t,))   # [..., i, j] = x_i
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, a, b, c, chunk, dtype=F32):
    """x (B,L,H,P) already times dt; a (B,L,H) = A·dt; b, c (B,L,N).
    Returns y (B,L,H,P)."""
    bs, ln, h, p = x.shape
    nc = ln // chunk
    x = x.reshape(bs, nc, chunk, h, p)
    b = b.reshape(bs, nc, chunk, -1)
    c = c.reshape(bs, nc, chunk, -1)
    a = jnp.moveaxis(a.reshape(bs, nc, chunk, h), -1, 1)   # (B,H,C,l)
    a_cs = jnp.cumsum(a, -1)
    decay = jnp.exp(segsum(a))                               # (B,H,C,l,s)
    y_diag = _mm("bcln,bcsn,bhcls,bcshp->bclhp", c, b, decay, x,
                 dtype=dtype)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)            # (B,H,C,l)
    states = _mm("bcln,bhcl,bclhp->bchpn", b, decay_states, x, dtype=dtype)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = _mm("bhzc,bchpn->bzhpn", decay_chunk, states, dtype=dtype)[:, :-1]
    y_off = _mm("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs),
                dtype=dtype)
    return (y_diag + y_off).reshape(bs, ln, h, p)


def block(lp: Dict, x, cfg: Dict, dtype=F32):
    d, n, p = cfg["d_model"], cfg["ssm_state"], cfg["ssm_headdim"]
    di = cfg["ssm_expand"] * d
    h, k, eps = di // p, cfg["d_conv"], cfg["norm_eps"]
    xn = rmsnorm(x, lp["norm"], eps)
    proj = _mm("bld,de->ble", xn, lp["w_in"], dtype=dtype)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + xbc.shape[1]] * lp["conv_w"][i]
               for i in range(k))
    xbc = jax.nn.silu(conv)
    xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    xh = xs.reshape(xs.shape[:2] + (h, p))
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # (B,L,H)
    a = -jnp.exp(lp["a_log"])
    y = ssd(xh * dt[..., None], a * dt, bm, cm, cfg["ssm_chunk"], dtype)
    y = y + lp["d_skip"][:, None] * xh
    y = rmsnorm(y.reshape(xs.shape) * jax.nn.silu(z), lp["out_norm"], eps)
    return x + _mm("ble,ed->bld", y, lp["w_out"], dtype=dtype)


def loss(params: Dict, tokens, targets, cfg: Dict, dtype=F32):
    x = params["embed"][tokens].astype(F32)

    def body(x, lp):
        return block(lp, x, cfg, dtype), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    x = rmsnorm(x, params["final_norm"], cfg["norm_eps"])
    logits = _mm("bld,dv->blv", x, params["unembed"], dtype=dtype)
    logz = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def adamw(params, grads, mu, nu, step: int, opt: Dict):
    """One AdamW update (step counts from 1)."""
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat, vhat = m / (1 - b1 ** step), v / (1 - b2 ** step)
        p = p - opt["lr"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                             + opt["weight_decay"] * p)
        return p, m, v

    out = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)  # noqa
    return pick(0), pick(1), pick(2)
