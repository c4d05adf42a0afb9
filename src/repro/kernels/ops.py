"""Jit'd public wrappers around the Pallas kernels.

``use_pallas`` selects the kernel vs the pure-jnp reference.  Whether a
kernel runs compiled or in the Pallas interpreter is decided here, from
the platform (``interpret_mode``): compiled on the accelerator, the
interpreter only where JAX runs on the CPU (tests, rehearsals).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention as _flash
from .gc_compact import gather_page_blocks
from .paged_attention import paged_attention as _paged
from .ssd_scan import ssd_scan as _ssd


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """True where Pallas kernels must run in the interpreter: JAX's default
    platform is the CPU.  An explicit ``interpret`` (tests) wins."""
    if interpret is not None:
        return interpret
    return jax.devices()[0].platform == "cpu"


def attention(q, k, v, causal: bool = True, use_pallas: bool = False,
              interpret: Optional[bool] = None):
    if use_pallas:
        return _flash(q, k, v, causal=causal,
                      interpret=interpret_mode(interpret))
    return ref.flash_attention_ref(q, k, v, causal=causal)


def decode_attention(q, k_pool, v_pool, page_table, lengths,
                     use_pallas: bool = False,
                     interpret: Optional[bool] = None):
    if use_pallas:
        return _paged(q, k_pool, v_pool, page_table, lengths,
                      interpret=interpret_mode(interpret))
    return ref.paged_attention_ref(q, k_pool, v_pool, page_table, lengths)


def gmm(lhs, rhs, group_sizes, interpret: Optional[bool] = None):
    """Grouped matmul (megablox, forward and backward): the rows of group
    i of ``lhs`` (M, K) times ``rhs[i]`` (K, N) for the ``len(rhs)`` first
    groups of ``group_sizes``; the rows of any further group come out zero.
    Tiles of 512 rows (M a multiple of them), 1024 of K and of N: on one
    v5e they took 8.7 ms for an expert MLP's forward and backward (8 groups
    of 2688 x 1856, 6131 of 98304 rows) where 128-tiles took 39.6 ms and
    ``jax.lax.ragged_dot`` 20.9 ms."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    m, k = lhs.shape
    tiling = (min(512, m), min(1024, k), min(1024, rhs.shape[-1]))
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None,
                        False, interpret_mode(interpret))


def ssd(x, dt, a, bmat, cmat, chunk: int = 128, use_pallas: bool = False,
        interpret: Optional[bool] = None):
    if use_pallas:
        return _ssd(x, dt, a, bmat, cmat, chunk=chunk,
                    interpret=interpret_mode(interpret))
    return ref.ssd_scan_ref(x, dt, a, bmat, cmat)


# --------------------------------------------------------------------------
# GC compaction planning (host side) + kernel dispatch
# --------------------------------------------------------------------------

def compact_plan(valid: np.ndarray, block_pages: int
                 ) -> Tuple[np.ndarray, np.ndarray, List[Tuple[int, int]]]:
    """Turn a page-validity bitmap into a run-coalesced copy plan.

    Returns (block_src_ids, tail_page_ids, runs):
    * ``block_src_ids`` — source *block* indices (block_pages-aligned runs
      of live pages) to move with one large DMA each;
    * ``tail_page_ids`` — leftover live pages moved at single-page
      granularity;
    * ``runs`` — [(start, length)] of the detected live runs (for stats:
      DMA count = len(block_src_ids) + len(tail_page_ids) vs
      valid.sum() without coalescing — the paper's Fig. 10 arithmetic).
    """
    valid = np.asarray(valid, bool)
    runs: List[Tuple[int, int]] = []
    i = 0
    n = len(valid)
    while i < n:
        if not valid[i]:
            i += 1
            continue
        j = i
        while j + 1 < n and valid[j + 1]:
            j += 1
        runs.append((i, j - i + 1))
        i = j + 1
    blocks: List[int] = []
    tail: List[int] = []
    for start, length in runs:
        # aligned full blocks inside the run
        first_block = -(-start // block_pages)          # ceil
        last_block = (start + length) // block_pages
        for b in range(first_block, last_block):
            blocks.append(b)
        covered = set(range(first_block * block_pages,
                            last_block * block_pages))
        for p in range(start, start + length):
            if p not in covered:
                tail.append(p)
    return (np.asarray(blocks, np.int32), np.asarray(tail, np.int32), runs)


def compact_pages(pool, valid, block_pages: int = 4,
                  use_pallas: bool = False,
                  interpret: Optional[bool] = None):
    """Compact live pages to the front of a fresh pool, run-coalesced.

    Returns (packed_pages, new_index, dma_count) where ``new_index[i]`` is
    the destination slot of old page i (−1 if dropped) and ``dma_count``
    is the number of copy DMAs issued (the adaptive-readahead win).
    """
    valid_np = np.asarray(valid, bool)
    if not use_pallas:
        packed, new_index = ref.compact_pages_ref(pool, jnp.asarray(valid_np))
        return packed, new_index, int(valid_np.sum())
    return pack_pages(pool, compact_plan(valid_np, block_pages),
                      block_pages, interpret)


def pack_pages(pool, plan, block_pages: int = 4,
               interpret: Optional[bool] = None):
    """The Pallas path of :func:`compact_pages`, for a ``plan`` that
    :func:`compact_plan` has already made; same return values."""
    blocks, tail, _ = plan
    interpret = interpret_mode(interpret)
    parts = []
    if len(blocks):
        parts.append(gather_page_blocks(pool, jnp.asarray(blocks),
                                        block_pages=block_pages,
                                        interpret=interpret))
    if len(tail):
        parts.append(gather_page_blocks(pool, jnp.asarray(tail),
                                        block_pages=1, interpret=interpret))
    live_pages = (jnp.concatenate(parts, axis=0) if parts
                  else jnp.zeros((0,) + pool.shape[1:], pool.dtype))
    # order: block pages first then tails — build matching new_index
    order = np.concatenate([
        np.concatenate([np.arange(b * block_pages, (b + 1) * block_pages)
                        for b in blocks]) if len(blocks) else
        np.zeros((0,), np.int64),
        tail.astype(np.int64)])
    new_index = np.full(pool.shape[0], -1, np.int32)
    new_index[order] = np.arange(len(order), dtype=np.int32)
    n_live = len(order)
    padded = jnp.zeros_like(pool)
    packed = padded.at[:n_live].set(live_pages)
    return packed, jnp.asarray(new_index), len(blocks) + len(tail)
