"""Train / prefill / decode step builders with explicit shardings.

``build_train_step`` returns (fn, in_shardings, out_shardings, abstract
inputs) ready for ``jax.jit(...).lower(...)`` — the dry-run consumes
exactly this.  Gradient accumulation (microbatching) runs as a
``lax.scan`` over global-batch splits; buffers are donated.  The forward,
loss and backward run under the name scope ``train.loss`` and the AdamW
update under ``train.optimizer``, so a profile's op lines split the step.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import get_model
from ..models.config import ModelConfig
from ..parallel.ctx import activation_rules
from ..parallel.sharding import (Rules, default_rules, spec_for,
                                 tree_shardings)
from .optimizer import AdamWConfig, apply_updates, init_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    adamw: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    grad_compression: bool = False   # int8 DP all-reduce (shard_map path)


def batch_specs(cfg: ModelConfig, batch_abstract: Dict, rules: Rules,
                mesh: Mesh):
    out = {}
    for k, v in batch_abstract.items():
        axes = ["batch"] + [None] * (len(v.shape) - 1)
        out[k] = spec_for(v.shape, tuple(axes), rules, mesh)
    return out


def make_batch_abstract(cfg: ModelConfig, global_batch: int, seq: int
                        ) -> Dict[str, jax.ShapeDtypeStruct]:
    b, s = global_batch, seq
    batch: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.frontend == "none":
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:
        batch["frames"] = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.bfloat16)
    pos_shape = (b, s, 3) if cfg.rope == "mrope" else (b, s)
    batch["positions"] = jax.ShapeDtypeStruct(pos_shape, jnp.int32)
    batch["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return batch


def build_train_step(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                     seq: int, tc: Optional[TrainConfig] = None,
                     rules: Optional[Rules] = None):
    tc = tc or TrainConfig()
    rules = rules or default_rules(mesh)
    model = get_model(cfg)
    params_abs = model.init(cfg, abstract=True)
    axes = model.logical_axes(cfg)
    opt_abs = init_state(params_abs, tc.adamw, abstract=True)
    batch_abs = make_batch_abstract(cfg, global_batch, seq)

    p_shard = tree_shardings(params_abs, axes, rules, mesh)
    mu_shard = tree_shardings(opt_abs["mu"], axes, rules, mesh)
    opt_shard = {"mu": mu_shard, "nu": mu_shard,
                 "count": NamedSharding(mesh, P())}
    b_spec = batch_specs(cfg, batch_abs, rules, mesh)
    b_shard = {k: NamedSharding(mesh, v) for k, v in b_spec.items()}

    def train_step(params, opt_state, batch):
      with activation_rules(mesh, rules):
        if tc.microbatches > 1:
            def micro(i, batch=batch):
                return jax.tree.map(
                    lambda x: x.reshape((tc.microbatches,
                                         x.shape[0] // tc.microbatches)
                                        + x.shape[1:])[i], batch)

            def body(carry, i):
                acc = carry
                with jax.named_scope("train.loss"):
                    loss, g = jax.value_and_grad(model.loss_fn)(
                        params, micro(i), cfg)
                return jax.tree.map(jnp.add, acc,
                                    {"g": g, "loss": loss}), None

            zero = {"g": jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
                "loss": jnp.zeros((), jnp.float32)}
            acc, _ = jax.lax.scan(body, zero,
                                  jnp.arange(tc.microbatches))
            grads = jax.tree.map(lambda g: g / tc.microbatches, acc["g"])
            loss = acc["loss"] / tc.microbatches
        else:
            with jax.named_scope("train.loss"):
                loss, grads = jax.value_and_grad(model.loss_fn)(
                    params, batch, cfg)
        with jax.named_scope("train.optimizer"):
            new_params, new_opt = apply_updates(params, grads, opt_state,
                                                tc.adamw)
        metrics = {"loss": loss,
                   "grad_norm": jnp.sqrt(sum(
                       jnp.sum(jnp.square(g.astype(jnp.float32)))
                       for g in jax.tree.leaves(grads)))}
        return new_params, new_opt, metrics

    in_shardings = (p_shard, opt_shard, b_shard)
    out_shardings = (p_shard, opt_shard,
                     {"loss": NamedSharding(mesh, P()),
                      "grad_norm": NamedSharding(mesh, P())})
    abstract_inputs = (params_abs, opt_abs, batch_abs)
    return train_step, in_shardings, out_shardings, abstract_inputs


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                       seq: int, rules: Optional[Rules] = None):
    rules = rules or default_rules(mesh)
    model = get_model(cfg)
    params_abs = model.init(cfg, abstract=True)
    axes = model.logical_axes(cfg)
    batch_abs = make_batch_abstract(cfg, global_batch, seq)
    batch_abs.pop("targets")
    p_shard = tree_shardings(params_abs, axes, rules, mesh)
    b_spec = batch_specs(cfg, batch_abs, rules, mesh)
    b_shard = {k: NamedSharding(mesh, v) for k, v in b_spec.items()}

    def prefill_step(params, batch):
        with activation_rules(mesh, rules):
            logits = model.forward(params, batch, cfg)
            # serving returns last-token logits only (sampler input)
            return logits[:, -1, :]

    out_shard = NamedSharding(mesh, spec_for(
        (global_batch, cfg.vocab), ("batch", "vocab"), rules, mesh))
    return (prefill_step, (p_shard, b_shard), out_shard,
            (params_abs, batch_abs))


def cache_axes(cfg: ModelConfig):
    if cfg.family == "ssm":
        return {"conv": ("layers", "batch", "conv_k", "inner_conv"),
                "ssm": ("layers", "batch", "ssm_heads", "head_dim",
                        "ssm_state")}
    if cfg.family == "hybrid":
        return {"kv": ("layers", "kv2", "batch", "cache_seq", "kv_heads",
                       "head_dim"),
                "conv": ("layers", "layers2", "batch", "conv_k",
                         "inner_conv"),
                "ssm": ("layers", "layers2", "batch", "ssm_heads",
                        "head_dim", "ssm_state")}
    return ("layers", "kv2", "batch", "cache_seq", "kv_heads", "head_dim")


def build_decode_step(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                      max_seq: int, rules: Optional[Rules] = None):
    """One-token serve_step against a max_seq KV cache (or SSM state)."""
    rules = rules or default_rules(mesh)
    model = get_model(cfg)
    params_abs = model.init(cfg, abstract=True)
    axes = model.logical_axes(cfg)
    p_shard = tree_shardings(params_abs, axes, rules, mesh)

    if cfg.family == "ssm":
        cache_abs = model.init_cache(cfg, global_batch, abstract=True)
    else:
        cache_abs = model.init_cache(cfg, global_batch, max_seq,
                                     abstract=True)
    ca = cache_axes(cfg)
    if isinstance(cache_abs, dict):
        c_shard = {k: NamedSharding(
            mesh, spec_for(cache_abs[k].shape, ca[k], rules, mesh))
            for k in cache_abs}
    else:
        c_shard = NamedSharding(mesh,
                                spec_for(cache_abs.shape, ca, rules, mesh))
    bshape = (global_batch,)
    l_shard = NamedSharding(mesh, spec_for(bshape, ("batch",), rules, mesh))
    t_shard = NamedSharding(mesh, spec_for(bshape + (1,),
                                           ("batch", None), rules, mesh))
    lengths_abs = jax.ShapeDtypeStruct(bshape, jnp.int32)
    tokens_abs = jax.ShapeDtypeStruct(bshape + (1,), jnp.int32)
    logits_shard = NamedSharding(mesh, spec_for(
        (global_batch, 1, cfg.vocab), ("batch", None, "vocab"), rules, mesh))

    def serve_step(params, cache, lengths, tokens):
        with activation_rules(mesh, rules):
            return model.decode_step(params, cache, lengths, tokens, cfg)

    in_shardings = (p_shard, c_shard, l_shard, t_shard)
    out_shardings = (logits_shard, c_shard)
    abstract_inputs = (params_abs, cache_abs, lengths_abs, tokens_abs)
    return serve_step, in_shardings, out_shardings, abstract_inputs
