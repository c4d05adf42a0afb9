"""Paged decode attention — Pallas TPU kernel.

One query token per sequence attends to a KV cache stored as fixed-size
pages in a global pool, indirected through a page table (the Scavenger+
"index → value-store" layout on HBM).

Grid: (batch, n_pages) with the page dimension innermost (sequential) so
an online softmax accumulates in VMEM scratch.  The page table rides in
scalar-prefetch: the KV BlockSpec index maps dereference
``page_table[b, p]`` so each grid step DMAs exactly one *physical* page
from the pool — gather happens in the DMA engine, not the VPU.

Each step moves a whole page, every kv head at once: the wrapper views a
page ``(page_size, Hkv, D)`` as ``(page_size·Hkv, D)`` rows (a free
reshape), which keeps the block's last two dimensions legal for Mosaic
whatever ``Hkv`` is.  All query heads score against all rows in one MXU
matmul, and a mask keeps each head to its own kv head's rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(page_table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size: int, n_pages: int,
            hkv: int, group: int, sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * sm_scale       # (H, d)
    k = k_ref[...].astype(jnp.float32)                  # (page·Hkv, d)
    v = v_ref[...].astype(jnp.float32)

    length = lengths_ref[b]
    page_id = page_table_ref[b, p]
    shape = (q.shape[0], page_size * hkv)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    pos = p * page_size + col // hkv
    valid = ((col % hkv == row // group) & (pos < length)
             & (page_id >= 0))

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(valid, s, NEG_INF)                    # (H, page·Hkv)
    m_prev, l_prev = m_ref[...], l_ref[...]             # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_prev * alpha + pexp.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pexp, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, page_table, lengths,
                    interpret: bool = False):
    """q: (B, H, D); k/v_pool: (P, page, Hkv, D);
    page_table: (B, n_pages) int32 (−1 = unmapped); lengths: (B,).
    Returns (B, H, D)."""
    b, h, d = q.shape
    p_total, page_size, hkv, _ = k_pool.shape
    n_pages = page_table.shape[1]
    rows = page_size * hkv

    # negative page ids must still produce a safe DMA address
    safe_table = jnp.maximum(page_table, 0).astype(jnp.int32)

    def q_map(bi, p, *refs):
        return (bi, 0, 0)

    def kv_map(bi, p, table_ref, lengths_ref):
        return (table_ref[bi, p], 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, n_pages=n_pages,
                          hkv=hkv, group=h // hkv,
                          sm_scale=1.0 / math.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=[
                pl.BlockSpec((None, h, d), q_map),
                pl.BlockSpec((None, rows, d), kv_map),
                pl.BlockSpec((None, rows, d), kv_map),
            ],
            out_specs=pl.BlockSpec((None, h, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(safe_table, lengths.astype(jnp.int32), q,
      k_pool.reshape(p_total, rows, d), v_pool.reshape(p_total, rows, d))
