"""Nemotron-H hybrid (``model_type`` nemotron_h; Nemotron-3-Nano-30B-A3B):
one mixer per layer, typed by the published ``hybrid_override_pattern``:
``M`` Mamba-2 (grouped SSD, conv bias), ``E`` the expert layer of
``models.moe`` (sigmoid router, relu² experts, one shared expert), ``*``
GQA attention with no positional encoding.

Every layer is ``x ← x + mixer(RMSNorm(x))``; a final RMSNorm and an
untied head follow.  The layers are built from the first ``n_layers``
characters of ``cfg.layer_pattern``, each with its own parameters under
``blocks/l<i>_<kind>``, and run unrolled, each under its own remat.
After each training step :func:`update_state` moves every expert layer's
selection bias against its load (``moe.rebias``); no gradient trains it.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import moe
from .config import ModelConfig
from .modules import (ParamSpec, attention_specs, axes_tree, gqa_attention,
                      materialize, norm)
from .ssm import ssd_layer, ssd_layer_specs

Params = Dict[str, Any]
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def layer_names(cfg: ModelConfig):
    return [f"l{i}_{KINDS[c]}" for i, c in enumerate(cfg.pattern())]


def specs(cfg: ModelConfig) -> Params:
    blocks = {}
    for name, c in zip(layer_names(cfg), cfg.pattern()):
        if c == "M":
            blocks[name] = ssd_layer_specs(cfg)
        else:
            blocks[name] = {"norm": ParamSpec((cfg.d_model,), ("embed",)),
                            KINDS[c]: (moe.specs(cfg) if c == "E"
                                       else attention_specs(cfg))}
    return {
        "embed": ParamSpec((cfg.vocab, cfg.d_model),
                           ("vocab_in", "embed_in")),
        "blocks": blocks,
        "final_norm": ParamSpec((cfg.d_model,), ("embed",)),
        "unembed": ParamSpec((cfg.d_model, cfg.vocab), ("embed", "vocab")),
    }


def init(cfg: ModelConfig, rng=None, abstract: bool = False) -> Params:
    return materialize(specs(cfg), rng, abstract, cfg.param_dtype)


def logical_axes(cfg: ModelConfig) -> Params:
    return axes_tree(specs(cfg))


def _layer(kind: str, cfg: ModelConfig, lp: Params, x, positions):
    """One layer; returns (x, the expert layer's routing counts or None)."""
    from ..parallel.ctx import constrain
    x = constrain(x, ("act_batch", None, None))
    if kind == "M":
        return ssd_layer(lp, x, cfg), None
    xn = norm(x, lp["norm"], cfg)
    if kind == "E":
        y, counts = moe.moe(lp["moe"], xn, cfg)
        return x + y, counts
    y, _ = gqa_attention(lp["attn"], xn, positions, cfg, causal=True)
    return x + y, None


def forward_with_stats(params: Params, batch: Dict, cfg: ModelConfig
                       ) -> Tuple[jax.Array, Dict]:
    """Logits (B, S, V) and the expert layers' routing counts, stacked:
    {"moe_pairs": pairs per held expert, (n_E, E_held) int32; "moe_load":
    pairs per expert, (n_E, n_experts) int32}."""
    x = params["embed"].astype(cfg.compute_dtype)[batch["tokens"]]
    counts = []
    for name, c in zip(layer_names(cfg), cfg.pattern()):
        fn = lambda lp, x, pos, c=c: _layer(c, cfg, lp, x, pos)  # noqa
        if cfg.remat == "full":
            fn = jax.checkpoint(fn)
        x, p = fn(params["blocks"][name], x, batch["positions"])
        if p is not None:
            counts.append(p)
    x = norm(x, params["final_norm"], cfg)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        params["unembed"].astype(cfg.compute_dtype))
    stats = ({k: jnp.stack([c[k] for c in counts]) for k in counts[0]}
             if counts else {})
    return logits, stats


def forward(params: Params, batch: Dict, cfg: ModelConfig) -> jax.Array:
    return forward_with_stats(params, batch, cfg)[0]


def loss_and_stats(params: Params, batch: Dict, cfg: ModelConfig):
    """(mean next-token cross-entropy, the expert layers' routing counts)."""
    logits, stats = forward_with_stats(params, batch, cfg)
    logits = logits.astype(jnp.float32)
    targets = batch["targets"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1).squeeze(-1)
    mask = (targets >= 0).astype(jnp.float32)
    loss = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, stats


def loss_fn(params: Params, batch: Dict, cfg: ModelConfig) -> jax.Array:
    return loss_and_stats(params, batch, cfg)[0]


def update_state(old: Params, new: Params, stats: Dict, cfg: ModelConfig
                 ) -> Params:
    """``new`` (the optimizer's step from ``old``) with each expert layer's
    selection bias set to ``old``'s moved against the step's load
    (``moe.rebias``)."""
    if "moe_load" not in stats:
        return new
    blocks = dict(new["blocks"])
    names = [n for n, c in zip(layer_names(cfg), cfg.pattern()) if c == "E"]
    for name, load in zip(names, stats["moe_load"]):
        lp = old["blocks"][name]["moe"]
        blocks[name] = dict(blocks[name], moe=dict(
            blocks[name]["moe"],
            router_bias=moe.rebias(lp["router_bias"], load)))
    return dict(new, blocks=blocks)
