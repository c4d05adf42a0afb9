"""Mamba-2 (SSD — state-space duality) layers and the pure-SSM model.

The chunked SSD algorithm follows the paper arXiv:2405.21060: intra-chunk
attention-like block (dense matmuls → MXU-friendly) plus an inter-chunk
state recurrence (``lax.scan`` over chunks).  ``repro.kernels.ssd_scan``
implements the same contract as a Pallas kernel; this jnp version is the
oracle and the dry-run path.

Decode keeps O(1) state per layer: a (B, H, P, N) SSM state and a rolling
depthwise-conv window — this is why mamba2/jamba run the ``long_500k``
shape that quadratic-attention models skip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .modules import ParamSpec, axes_tree, materialize, norm, rmsnorm

Params = Dict[str, Any]
D_CONV = 4


def ssd_layer_specs(cfg: ModelConfig) -> Params:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    conv_dim = di + 2 * cfg.ssm_groups * cfg.ssm_state
    specs = {
        "norm": ParamSpec((d,), ("embed",)),
        "w_in": ParamSpec((d, di + conv_dim + h), ("embed", "inner_all")),
        "conv_w": ParamSpec((D_CONV, conv_dim), ("conv_k", "inner_conv")),
        "a_log": ParamSpec((h,), ("ssm_heads",)),
        "d_skip": ParamSpec((h,), ("ssm_heads",)),
        "dt_bias": ParamSpec((h,), ("ssm_heads",)),
        "out_norm": ParamSpec((di,), ("inner",)),
        "w_out": ParamSpec((di, d), ("inner", "embed")),
    }
    if cfg.conv_bias:
        specs["conv_b"] = ParamSpec((conv_dim,), ("inner_conv",))
    return specs


def _split_proj(cfg: ModelConfig, proj):
    """(z, xBC, dt) of the input projection."""
    di = cfg.d_inner
    conv_dim = di + 2 * cfg.ssm_groups * cfg.ssm_state
    return (proj[..., :di], proj[..., di:di + conv_dim],
            proj[..., di + conv_dim:])


def _split_xbc(cfg: ModelConfig, xbc):
    """x (..., H, P), B and C (..., G, N) of the conv's output."""
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (cfg.ssm_heads, cfg.ssm_headdim)),
            xbc[..., di:di + g * n].reshape(lead + (g, n)),
            xbc[..., di + g * n:].reshape(lead + (g, n)))


def _gated_norm(y, z, gain, cfg: ModelConfig):
    """RMSNorm of y·silu(z) over each group's d_inner / G channels, in
    float32, times the gain."""
    v = y * jax.nn.silu(z)
    lead, g = v.shape[:-1], cfg.ssm_groups
    if g == 1:
        return rmsnorm(v, gain, cfg.norm_eps)
    v32 = v.astype(jnp.float32).reshape(lead + (g, v.shape[-1] // g))
    v32 = v32 * jax.lax.rsqrt(jnp.mean(v32 * v32, axis=-1, keepdims=True)
                              + cfg.norm_eps)
    return (v32.reshape(v.shape) * gain).astype(v.dtype)


def _causal_conv(xbc, conv_w, conv_b=None):
    """Depthwise causal conv along seq: xbc (B,S,C), conv_w (K,C), an
    optional bias (C,); SiLU after."""
    k = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * conv_w[i][None, None, :]
              for i in range(k))
    if conv_b is not None:
        out = out + conv_b
    return jax.nn.silu(out)


def _segsum_exp(dA_cs):
    """exp(segsum): lower-triangular decay matrix per chunk.
    dA_cs: (..., cl) cumulative sums → (..., cl, cl).

    The mask is applied BEFORE the exp (−inf → 0) so the masked branch
    cannot overflow and poison gradients (the where-grad pitfall)."""
    diff = dA_cs[..., :, None] - dA_cs[..., None, :]
    cl = dA_cs.shape[-1]
    mask = jnp.tril(jnp.ones((cl, cl), bool))
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


def ssd_chunked(x, dt, a, bmat, cmat, chunk: int,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SSD scan. x:(B,S,H,P) dt:(B,S,H) a:(H,)<0 bmat/cmat:(B,S,G,N), head
    h reading group h // (H/G).
    Returns (y:(B,S,H,P), final_state:(B,H,P,N)).

    Heads are laid out (G, R) with R = H/G heads a group; with one group
    the group axis is dropped, so that B and C broadcast over every head
    as in the ungrouped scan."""
    b, s, h, p = x.shape
    g, n = bmat.shape[-2:]
    assert s % chunk == 0 and h % g == 0, (s, chunk, h, g)
    nc = s // chunk
    G = "g" if g > 1 else ""                     # the group axis, if any
    heads = (g, h // g) if g > 1 else (h,)       # ([G,] R)
    xc = x.reshape(b, nc, chunk, *heads, p)
    dtc = dt.reshape(b, nc, chunk, *heads)
    bc = bmat.reshape(b, nc, chunk, *heads[:-1], n)
    cc = cmat.reshape(b, nc, chunk, *heads[:-1], n)

    dA = dtc * a.reshape(heads)                    # (B,nc,cl,[G,]R)
    dA_cs = jnp.cumsum(dA, axis=2)                 # (B,nc,cl,[G,]R)
    decay = _segsum_exp(jnp.moveaxis(dA_cs, 2, -1))    # (B,nc,[G,]R,cl,cl)

    xdt = xc * dtc[..., None]                      # (B,nc,cl,[G,]R,P)
    scores = jnp.einsum(f"bci{G}n,bcj{G}n->bc{G}ij", cc, bc)
    gated = decay * scores[..., None, :, :]        # (B,nc,[G,]R,cl,cl)
    y_diag = jnp.einsum(f"bc{G}rij,bcj{G}rp->bci{G}rp", gated, xdt)

    # chunk-final states: sum_j exp(dA_sum - dA_cs_j) dt_j B_j x_j
    dA_sum = dA_cs[:, :, -1:]                      # (B,nc,1,[G,]R)
    state_decay = jnp.exp(dA_sum - dA_cs)          # (B,nc,cl,[G,]R)
    chunk_states = jnp.einsum(f"bcj{G}n,bcj{G}r,bcj{G}rp->bc{G}rpn",
                              bc, state_decay * dtc, xc)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_sum[:, :, 0])         # (B,nc,[G,]R)
    init = (jnp.zeros((b, *heads, p, n), x.dtype) if initial_state is None
            else initial_state.reshape(b, *heads, p, n))

    def scan_fn(carry, inp):
        cs, cd = inp                               # (B,[G,]R,P,N), (B,[G,]R)
        new = carry * cd[..., None, None] + cs
        return new, carry                          # emit state *entering*

    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(chunk_states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,nc,[G,]R,P,N)

    in_decay = jnp.exp(dA_cs)                      # (B,nc,cl,[G,]R)
    y_off = jnp.einsum(f"bci{G}n,bc{G}rpn,bci{G}r->bci{G}rp", cc,
                       prev_states, in_decay)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, final.reshape(b, h, p, n)


def ssd_layer(lp: Params, x, cfg: ModelConfig,
              initial_state: Optional[jax.Array] = None,
              return_state: bool = False):
    """Full Mamba-2 block: in-proj → conv → SSD → gated out-proj."""
    from ..parallel.ctx import constrain
    x = constrain(x, ("act_batch", None, None))
    xn = norm(x, lp["norm"], cfg)
    proj = (xn @ lp["w_in"].astype(cfg.compute_dtype))
    z, xbc, dt = _split_proj(cfg, proj)
    conv_b = lp.get("conv_b")
    xbc = _causal_conv(xbc, lp["conv_w"].astype(cfg.compute_dtype),
                       None if conv_b is None
                       else conv_b.astype(cfg.compute_dtype))
    xh, bmat, cmat = _split_xbc(cfg, xbc)
    dt_soft = jax.nn.softplus(dt.astype(jnp.float32)
                              + lp["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    with jax.named_scope("ssd"):
        y, state = ssd_chunked(xh.astype(jnp.float32), dt_soft, a,
                               bmat.astype(jnp.float32),
                               cmat.astype(jnp.float32), cfg.ssm_chunk,
                               initial_state)
    y = y + lp["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(z.shape).astype(cfg.compute_dtype)
    y = _gated_norm(y, z, lp["out_norm"], cfg)
    out = y @ lp["w_out"].astype(cfg.compute_dtype)
    if return_state:
        return x + out, state
    return x + out


def ssd_decode_step(lp: Params, x1, conv_state, ssm_state, cfg: ModelConfig):
    """Single-token decode.  x1: (B,1,D); conv_state: (B,K-1,conv_dim);
    ssm_state: (B,H,P,N).  Returns (y1, new_conv_state, new_ssm_state)."""
    xn = norm(x1, lp["norm"], cfg)
    proj = xn @ lp["w_in"].astype(cfg.compute_dtype)
    z, xbc, dt = _split_proj(cfg, proj)
    window = jnp.concatenate([conv_state, xbc], axis=1)      # (B,K,C)
    conv_w = lp["conv_w"].astype(cfg.compute_dtype)
    conv = jnp.einsum("bkc,kc->bc", window, conv_w)
    if "conv_b" in lp:
        conv = conv + lp["conv_b"].astype(cfg.compute_dtype)
    conv_out = jax.nn.silu(conv)
    new_conv_state = window[:, 1:]
    xh, bv, cv = _split_xbc(cfg, conv_out)                   # (B,H,P), (B,G,N)
    xh = xh.astype(jnp.float32)
    r = cfg.ssm_heads // cfg.ssm_groups
    bv = jnp.repeat(bv.astype(jnp.float32), r, axis=1)       # (B,H,N)
    cv = jnp.repeat(cv.astype(jnp.float32), r, axis=1)
    dt_soft = jax.nn.softplus(
        dt[:, 0].astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt_soft * a)                             # (B,H)
    new_state = ssm_state * decay[..., None, None] + \
        (dt_soft[..., None] * xh)[..., None] * bv[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, cv)
    y = y + lp["d_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(x1.shape[0], 1, cfg.d_inner).astype(cfg.compute_dtype)
    y = _gated_norm(y, z, lp["out_norm"], cfg)
    out = y @ lp["w_out"].astype(cfg.compute_dtype)
    return x1 + out, new_conv_state, new_state


# --------------------------------------------------------------------------
# Pure-SSM LM (mamba2-370m)
# --------------------------------------------------------------------------

def _stack(layer: Params, n: int) -> Params:
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            s.scale, s.dtype),
        layer, is_leaf=lambda x: isinstance(x, ParamSpec))


def specs(cfg: ModelConfig) -> Params:
    return {
        "embed": ParamSpec((cfg.vocab, cfg.d_model),
                           ("vocab_in", "embed_in")),
        "layers": _stack(ssd_layer_specs(cfg), cfg.n_layers),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",)),
        "unembed": ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }


def init(cfg: ModelConfig, rng=None, abstract: bool = False) -> Params:
    return materialize(specs(cfg), rng, abstract, cfg.param_dtype)


def logical_axes(cfg: ModelConfig) -> Params:
    return axes_tree(specs(cfg))


def forward(params: Params, batch: Dict, cfg: ModelConfig) -> jax.Array:
    x = params["embed"].astype(cfg.compute_dtype)[batch["tokens"]]

    def body(carry, lp):
        return ssd_layer(lp, carry, cfg), None

    body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    x, _ = jax.lax.scan(body_fn, x, params["layers"])
    x = norm(x, params["final_norm"], cfg)
    return jnp.einsum("bsd,dv->bsv", x,
                      params["unembed"].astype(cfg.compute_dtype))


def loss_fn(params: Params, batch: Dict, cfg: ModelConfig) -> jax.Array:
    logits = forward(params, batch, cfg).astype(jnp.float32)
    targets = batch["targets"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1).squeeze(-1)
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def init_cache(cfg: ModelConfig, batch: int, abstract: bool = False):
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    shapes = {
        "conv": (cfg.n_layers, batch, D_CONV - 1, conv_dim),
        "ssm": (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_headdim,
                cfg.ssm_state),
    }
    if abstract:
        return {"conv": jax.ShapeDtypeStruct(shapes["conv"],
                                             cfg.compute_dtype),
                "ssm": jax.ShapeDtypeStruct(shapes["ssm"], jnp.float32)}
    return {"conv": jnp.zeros(shapes["conv"], cfg.compute_dtype),
            "ssm": jnp.zeros(shapes["ssm"], jnp.float32)}


def decode_step(params: Params, cache, lengths, tokens, cfg: ModelConfig):
    x = params["embed"].astype(cfg.compute_dtype)[tokens]     # (B,1,D)

    def body(x, packed):
        lp, conv_s, ssm_s = packed
        y, nc, ns = ssd_decode_step(lp, x, conv_s, ssm_s, cfg)
        return y, (nc, ns)

    x, (new_conv, new_ssm) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"]))
    x = norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        params["unembed"].astype(cfg.compute_dtype))
    return logits, {"conv": new_conv, "ssm": new_ssm}
