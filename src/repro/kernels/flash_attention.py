"""Causal GQA flash attention (forward) — Pallas TPU kernel.

Streaming-softmax over KV blocks: for each (batch, q-head, q-block) grid
cell the kernel walks KV blocks of the same sequence, maintaining running
max/denominator in VMEM scratch, so the working set is
O(block_q·d + block_k·d) regardless of sequence length.  Block sizes are
MXU-aligned (multiples of 128 on the contracting dims).

GQA is expressed in the BlockSpec index maps: q-head ``h`` reads KV head
``h // (H // Hkv)`` — no materialized broadcast.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
            seq_len: int, causal: bool, sm_scale: float):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * sm_scale          # (bq, d)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)

    n_kv = seq_len // block_k
    # causal: kv blocks strictly after this q block contribute nothing
    if causal:
        kv_hi = ((qi + 1) * block_q + block_k - 1) // block_k  # ceil-div
    else:
        kv_hi = n_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, kv_hi, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, S, H, D); k/v: (B, S, Hkv, D) → (B, S, H, D)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)

    # heads-major views keep each block's last two dims (seq, d): Mosaic
    # cannot squeeze the head axis where it is the second-minor one
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    grid = (b, h, s // block_q)
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          seq_len=s, causal=causal, sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, g=g: (bi, hi // g, 0, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi, g=g: (bi, hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
