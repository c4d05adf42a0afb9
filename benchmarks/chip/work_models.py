"""Operations and bytes of the ``train_model`` cells, from their shapes.

As in ``work``: what the algorithm needs, 2 flops a multiply-add; a kernel
that pads, recomputes or reads more counts that against its own time.
Training is the forward and the backward, three times the forward's
matmul work; causal attention is counted at its mean of S/2 keys a query.
"""

from __future__ import annotations

from typing import Dict, Tuple


def nemotron_h_layer_flops(cfg: Dict, seq: int) -> Dict[str, float]:
    """Forward flops per token of each kind of layer, with the keys of the
    configuration file.  The expert layer counts the router, the shared
    expert and the routed experts at this chip's share: each token sends
    ``num_experts_per_tok`` pairs, of which held / total land here on
    average (``published`` gives the total)."""
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, cl = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    di = h * p
    conv_dim = di + 2 * g * n
    mamba = (2.0 * d * (di + conv_dim + h) + 2.0 * di * d
             + 2.0 * cfg["conv_kernel"] * conv_dim
             + 2.0 * cl * n * g + 2.0 * h * cl * p + 4.0 * h * p * n)
    total = cfg["published"]["n_routed_experts"]
    pairs = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / total
    f, sf = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    experts = 2.0 * d * total + pairs * 4.0 * d * f + 4.0 * d * sf
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    attn = (2.0 * d * (hq + 2 * hk) * hd + 2.0 * hq * hd * d
            + 4.0 * hq * hd * seq / 2)
    return {"M": mamba, "E": experts, "*": attn}


def nemotron_h_train_flops_per_token(cfg: Dict, seq: int) -> float:
    per = nemotron_h_layer_flops(cfg, seq)
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    fwd = (sum(per[c] for c in pattern)
           + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
    return 3.0 * fwd


def olmo_train_flops_per_token(cfg: Dict, seq: int) -> float:
    """OLMo's decoder: q/k/v/o projections, causal attention, a SwiGLU
    MLP (three matmuls) per layer, and the head."""
    d, h, hd, ff = cfg["d_model"], cfg["n_heads"], cfg["head_dim"], \
        cfg["d_ff"]
    layer = (2.0 * d * (h + 2 * cfg["kv_heads"]) * hd + 2.0 * h * hd * d
             + 4.0 * h * hd * seq / 2 + 6.0 * d * ff)
    return 3.0 * (cfg["n_layers"] * layer + 2.0 * d * cfg["vocab"])


def gmm_train(pairs: float, experts: int, d: int, f: int, calls: int = 1,
              nbytes: int = 2) -> Tuple[float, float]:
    """``calls`` expert MLPs (up d->f, down f->d), each over its layer's
    sorted rows, ``pairs`` rows in all, forward and backward: each
    projection is one grouped matmul forward and two backward (the rows'
    gradient and the experts' gradient), each reading its two operands and
    writing its result once."""
    flops = 3 * 2 * (2.0 * pairs * d * f)
    weights = experts * d * f * nbytes
    rows = pairs * (d + f) * nbytes
    # per projection: forward (rows in, weights, rows out), rows' gradient
    # (the same), experts' gradient (both row sets in, weights out)
    nbytes_total = 2 * 3 * (calls * weights + rows)
    return flops, nbytes_total


def attention_train(batch: int, seq: int, heads: int, kv_heads: int,
                    head_dim: int, nbytes: int = 2) -> Tuple[float, float]:
    """Causal attention of ``batch`` rows, forward and backward: QK^T and
    PV forward (S/2 keys a query), twice that backward; reading q, k, v
    and writing the output forward; reading them, the output and its
    gradient and writing q's, k's and v's gradients backward."""
    flops = 3 * 4.0 * batch * heads * head_dim * seq * seq / 2
    q = batch * seq * heads * head_dim * nbytes
    kv = 2 * batch * seq * kv_heads * head_dim * nbytes
    nbytes_total = (2 * q + kv) + (4 * q + 2 * kv)
    return flops, nbytes_total
