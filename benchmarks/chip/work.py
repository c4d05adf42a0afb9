"""Operations and bytes that a step or kernel requires, from its shapes.

What the algorithm needs, whatever implements it: a kernel that reads
padding or recomputes counts against its own time, not here.  The least
time on a chip is the larger of operations over its peak rate and bytes
over its memory bandwidth (``least_time``).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """(seconds, bound) of the faster of the two roofs' limits."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def paged_attention(lengths: Iterable[int], n_heads: int, kv_heads: int,
                    head_dim: int, kv_bytes: int, q_bytes: int = 4
                    ) -> Tuple[float, float]:
    """One decode token per sequence over ``lengths`` cached rows each:
    QK^T and PV (2 flops a multiply-add), reading every needed K and V row
    once, the queries in and the outputs out."""
    lengths = list(lengths)
    rows = sum(lengths)
    flops = 4.0 * n_heads * head_dim * rows
    nbytes = (2.0 * rows * kv_heads * head_dim * kv_bytes
              + 2.0 * len(lengths) * n_heads * head_dim * q_bytes)
    return flops, nbytes


def serve_decode_step(lengths: Iterable[int], d_model: int, n_heads: int,
                      kv_heads: int, head_dim: int, kv_bytes: int,
                      weight_bytes: int) -> Tuple[float, float]:
    """The serve loop's decode step on layer 0: the q/k/v projections of
    one token per sequence (reading their weights once), one K and one V
    row written per sequence, and the paged attention."""
    lengths = list(lengths)
    b = len(lengths)
    width = (n_heads + 2 * kv_heads) * head_dim
    flops = 2.0 * b * d_model * width
    nbytes = (d_model * width * weight_bytes + b * d_model * 4
              + 2.0 * b * kv_heads * head_dim * kv_bytes)
    af, ab = paged_attention(lengths, n_heads, kv_heads, head_dim, kv_bytes)
    return flops + af, nbytes + ab


def mamba2_train_flops_per_token(cfg: Dict) -> float:
    """Forward and backward (3x the forward) of a Mamba-2 LM per token:
    the in/out projections and the unembedding as matmuls (2 flops a
    multiply-add), the depthwise conv, and the chunked SSD of
    arXiv:2405.21060 (intra-chunk C·Bᵀ scores and their product with x,
    the chunk states and their read-out).  The embedding is a gather."""
    d, n, p = cfg["d_model"], cfg["ssm_state"], cfg["ssm_headdim"]
    di = cfg["ssm_expand"] * d
    h = di // p
    cl = cfg["ssm_chunk"]
    conv_dim = di + 2 * n
    proj = 2.0 * (d * (2 * di + 2 * n + h) + di * d)
    conv = 2.0 * cfg["d_conv"] * conv_dim
    ssd = 2.0 * cl * n + 2.0 * h * cl * p + 4.0 * h * p * n
    fwd = cfg["n_layers"] * (proj + conv + ssd) + 2.0 * d * cfg["vocab"]
    return 3.0 * fwd
