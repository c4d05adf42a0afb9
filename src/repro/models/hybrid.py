"""Jamba-style hybrid: Mamba + attention interleaved 1:7, MoE every other
layer (arXiv:2403.19887).

Layers are organized into super-blocks of ``attn_every`` (8) positions;
parameters are stacked per *position* across blocks, and a single
``lax.scan`` runs over blocks — HLO holds one block's code regardless of
depth.  Position roles (attention at index attn_every//2, MoE FFN on odd
positions) follow the Jamba paper's block diagram.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .modules import (ParamSpec, attention_specs, axes_tree, dense_ffn,
                      ffn, ffn_specs, gqa_attention, materialize, norm)
from .ssm import (D_CONV, ssd_decode_step, ssd_layer, ssd_layer_specs)

Params = Dict[str, Any]


def _position_roles(cfg: ModelConfig):
    """[(mixer, ffn_kind)] for each position within a super-block."""
    roles = []
    for i in range(cfg.attn_every):
        mixer = "attn" if i == cfg.attn_every // 2 else "mamba"
        ffn_kind = "moe" if (cfg.n_experts > 1
                             and i % cfg.moe_every == 1) else "dense"
        roles.append((mixer, ffn_kind))
    return roles


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, n_experts=1)


def _stack(layer: Params, n: int) -> Params:
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            s.scale, s.dtype),
        layer, is_leaf=lambda x: isinstance(x, ParamSpec))


def specs(cfg: ModelConfig) -> Params:
    n_blocks = cfg.n_layers // cfg.attn_every
    positions = {}
    for i, (mixer, ffn_kind) in enumerate(_position_roles(cfg)):
        layer: Params = {}
        if mixer == "attn":
            layer["attn_norm"] = ParamSpec((cfg.d_model,), ("embed",))
            layer["attn"] = attention_specs(cfg)
        else:
            layer["mamba"] = ssd_layer_specs(cfg)
        layer["ffn_norm"] = ParamSpec((cfg.d_model,), ("embed",))
        layer["ffn"] = ffn_specs(cfg if ffn_kind == "moe"
                                 else _dense_cfg(cfg))
        positions[f"pos{i}"] = _stack(layer, n_blocks)
    return {
        "embed": ParamSpec((cfg.vocab, cfg.d_model),
                           ("vocab_in", "embed_in")),
        "blocks": positions,
        "final_norm": ParamSpec((cfg.d_model,), ("embed",)),
        "unembed": ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }


def init(cfg: ModelConfig, rng=None, abstract: bool = False) -> Params:
    return materialize(specs(cfg), rng, abstract, cfg.param_dtype)


def logical_axes(cfg: ModelConfig) -> Params:
    return axes_tree(specs(cfg))


def _apply_position(cfg: ModelConfig, role, lp: Params, x, positions):
    from ..parallel.ctx import constrain
    x = constrain(x, ("act_batch", None, None))
    mixer, ffn_kind = role
    if mixer == "attn":
        h, _ = gqa_attention(lp["attn"], norm(x, lp["attn_norm"], cfg),
                             positions, cfg, causal=True)
        x = x + h
    else:
        x = ssd_layer(lp["mamba"], x, cfg)
    xn = norm(x, lp["ffn_norm"], cfg)
    if ffn_kind == "moe":
        x = x + ffn(lp["ffn"], xn, cfg)
    else:
        x = x + dense_ffn(lp["ffn"], xn, _dense_cfg(cfg))
    return x


def forward(params: Params, batch: Dict, cfg: ModelConfig) -> jax.Array:
    x = params["embed"].astype(cfg.compute_dtype)[batch["tokens"]]
    positions = batch["positions"]
    roles = _position_roles(cfg)

    def body(carry, block_params):
        y = carry
        for i, role in enumerate(roles):
            y = _apply_position(cfg, role, block_params[f"pos{i}"], y,
                                positions)
        return y, None

    body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    x, _ = jax.lax.scan(body_fn, x, params["blocks"])
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


# --------------------------------------------------------------------------
# Decode: attention positions carry a KV cache; mamba positions carry
# O(1) conv+SSM state — the reason jamba serves long_500k.
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               abstract: bool = False):
    n_blocks = cfg.n_layers // cfg.attn_every
    n_mamba = cfg.attn_every - 1
    conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    shapes = {
        "kv": (n_blocks, 2, batch, max_seq, cfg.kv_heads, cfg.head_dim),
        "conv": (n_blocks, n_mamba, batch, D_CONV - 1, conv_dim),
        "ssm": (n_blocks, n_mamba, batch, cfg.ssm_heads, cfg.ssm_headdim,
                cfg.ssm_state),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(
            v, jnp.float32 if k == "ssm" else cfg.compute_dtype)
            for k, v in shapes.items()}
    return {k: jnp.zeros(v, jnp.float32 if k == "ssm" else cfg.compute_dtype)
            for k, v in shapes.items()}


def decode_step(params: Params, cache, lengths, tokens, cfg: ModelConfig):
    from .modules import apply_rope
    b = tokens.shape[0]
    max_seq = cache["kv"].shape[3]
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    positions = lengths[:, None]
    kv_pos = jnp.arange(max_seq)[None, :]
    kv_pos = jnp.where(kv_pos <= lengths[:, None], kv_pos, -1)
    roles = _position_roles(cfg)

    def body(x, packed):
        block_params, kv_cache, conv_cache, ssm_cache = packed
        new_conv, new_ssm = [], []
        m = 0
        new_kv = kv_cache
        for i, role in enumerate(roles):
            lp = block_params[f"pos{i}"]
            mixer, ffn_kind = role
            if mixer == "attn":
                xn = norm(x, lp["attn_norm"], cfg)
                k_new = jnp.einsum("bsd,dhk->bshk", xn, lp["attn"]["wk"]) \
                    .astype(cfg.compute_dtype)
                v_new = jnp.einsum("bsd,dhk->bshk", xn, lp["attn"]["wv"]) \
                    .astype(cfg.compute_dtype)
                k_new = apply_rope(k_new, lengths[:, None], cfg.rope_theta)
                kc = kv_cache[0].at[jnp.arange(b), lengths].set(k_new[:, 0])
                vc = kv_cache[1].at[jnp.arange(b), lengths].set(v_new[:, 0])
                new_kv = jnp.stack([kc, vc])
                h, _ = gqa_attention(lp["attn"], xn, positions, cfg,
                                     causal=False, kv_override=(kc, vc),
                                     kv_positions=kv_pos)
                x = x + h
            else:
                y, nc, ns = ssd_decode_step(lp["mamba"], x,
                                            conv_cache[m], ssm_cache[m], cfg)
                x = y
                new_conv.append(nc)
                new_ssm.append(ns)
                m += 1
            xn = norm(x, lp["ffn_norm"], cfg)
            if ffn_kind == "moe":
                x = x + ffn(lp["ffn"], xn, cfg)
            else:
                x = x + dense_ffn(lp["ffn"], xn, _dense_cfg(cfg))
        return x, (new_kv, jnp.stack(new_conv), jnp.stack(new_ssm))

    x, (kv, conv, ssm) = jax.lax.scan(
        body, x, (params["blocks"], cache["kv"], cache["conv"],
                  cache["ssm"]))
    x = norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        params["unembed"].astype(cfg.compute_dtype))
    return logits, {"kv": kv, "conv": conv, "ssm": ssm}
