"""Model configuration shared by all architecture families."""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp


@dataclasses.dataclass
class ModelConfig:
    name: str
    family: str        # dense | moe | ssm | hybrid | nemotron_h | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int = 0
    kv_heads: int = 0
    d_ff: int = 0
    vocab: int = 32000

    # MoE (``models.moe``): routes over all ``n_experts``, holds
    # ``experts_held`` of them (0 = all), drops no token
    n_experts: int = 1
    top_k: int = 1
    experts_held: int = 0
    moe_every: int = 1            # jamba: MoE FFN every k-th layer
    router: str = "softmax"       # softmax | sigmoid (with a selection bias)
    routed_scale: float = 1.0     # times the routed experts' weights
    shared_ff: int = 0            # width of one shared expert (0 = none)

    # SSM (Mamba-2 / SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    ssm_groups: int = 1           # B/C groups; head h reads group h // (H/G)
    ssm_n_heads: int = 0          # 0 = expand * d_model / headdim
    conv_bias: bool = False
    attn_every: int = 0           # jamba: attention layer every k-th layer
    # nemotron_h: one mixer per layer, M (Mamba-2), E (MoE), * (attention);
    # the first n_layers characters are built
    layer_pattern: str = ""

    # misc
    rope: str = "rope"            # rope | mrope | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    ffn_act: str = "swiglu"       # swiglu | gelu | relu2
    ln_kind: str = "rms"          # rms | nonparametric
    norm_eps: float = 1e-6        # RMSNorm
    causal: bool = True           # False for encoder-only (hubert)
    frontend: str = "none"        # none | audio | vision (stubbed)
    sub_quadratic: bool = False   # True → long_500k decodable

    compute_dtype: object = jnp.bfloat16
    param_dtype: object = jnp.float32
    kv_cache_dtype: object = None     # e.g. jnp.float8_e4m3fn (decode opt)

    # remat: 'none' | 'full' | 'dots_with_no_batch_dims'
    remat: str = "full"
    scan_layers: bool = True

    # explicit head_dim (0 → d_model/n_heads); used when padding the head
    # count for shardability (§Perf cell B)
    head_dim_override: int = 0

    @property
    def head_dim(self) -> int:
        if self.head_dim_override:
            return self.head_dim_override
        return self.d_model // max(1, self.n_heads)

    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_experts

    def pattern(self) -> str:
        return self.layer_pattern[:self.n_layers]

    def _ssm_layer_params(self) -> int:
        d, di, h = self.d_model, self.d_inner, self.ssm_heads
        conv = di + 2 * self.ssm_groups * self.ssm_state
        return (d * (di + conv + h) + di * d + 4 * conv
                + (conv if self.conv_bias else 0) + 3 * h + di + d)

    def _nemotron_count(self, experts: int) -> int:
        d, hd = self.d_model, self.head_dim
        per = {
            "M": self._ssm_layer_params(),
            "E": (d * self.n_experts + self.n_experts
                  + experts * 2 * d * self.d_ff + 2 * d * self.shared_ff + d),
            "*": d * (self.n_heads + 2 * self.kv_heads) * hd
            + self.n_heads * hd * d + d,
        }
        return (sum(per[c] for c in self.pattern())
                + 2 * self.vocab * d + d)

    def param_count(self) -> int:
        """Total parameters (for 6·N·D roofline bookkeeping)."""
        if self.family == "nemotron_h":
            return self._nemotron_count(self.held_experts)
        d, ff, v = self.d_model, self.d_ff, self.vocab
        n_attn = self.n_layers
        n_ssm = 0
        if self.family == "ssm":
            n_attn, n_ssm = 0, self.n_layers
        elif self.family == "hybrid":
            n_attn = self.n_layers // max(1, self.attn_every)
            n_ssm = self.n_layers - n_attn
        total = 0
        if n_attn:
            hd = self.head_dim
            attn = d * self.n_heads * hd * 2 + d * self.kv_heads * hd * 2
            total += n_attn * attn
        if n_ssm:
            di, st, h = self.d_inner, self.ssm_state, self.ssm_heads
            ssm = d * (2 * di + 2 * st + h) + di * d + 4 * (di + 2 * st) \
                + 2 * h + di
            total += n_ssm * ssm
        # FFN: dense layers vs MoE layers
        if self.d_ff:
            n_moe = (self.n_layers // max(1, self.moe_every)
                     if self.n_experts > 1 else 0)
            n_dense = self.n_layers - n_moe
            mult = 3 if self.ffn_act == "swiglu" else 2
            total += n_dense * mult * d * ff
            total += n_moe * (self.n_experts * 3 * d * ff
                              + d * self.n_experts)
        total += 2 * v * d          # embed + unembed
        total += self.n_layers * 2 * d + d
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if self.n_experts <= 1:
            return self.param_count()
        if self.family == "nemotron_h":
            return self._nemotron_count(self.top_k)
        full = self.param_count()
        n_moe = self.n_layers // max(1, self.moe_every)
        moe_all = n_moe * self.n_experts * 3 * self.d_model * self.d_ff
        moe_active = n_moe * self.top_k * 3 * self.d_model * self.d_ff
        return full - moe_all + moe_active
