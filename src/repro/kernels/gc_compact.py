"""GC page compaction — the paper's adaptive-readahead insight on TPU.

Scavenger+ (III-B.4) batches GC validity results into a bitmap and copies
*contiguous runs* of live records with single large reads instead of one
I/O per record.  On TPU the analogous tier is the HBM page pool of the
serving KV-cache: compacting live pages with one DMA per multi-page run
instead of one gather per page.

The host (``ops.compact_plan``) turns the valid bitmap into a run-coalesced
copy plan at a fixed block granularity; the kernel is a pure data-mover
whose BlockSpec index map dereferences the scalar-prefetched source-block
ids — each grid step is exactly one (block_pages · page · D) DMA.

``move_pages`` is the mover the serving cache's compaction runs: it moves
only the pages whose index changes, every layer's K and V plane at once,
in place in the donated pool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ids_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("block_pages", "interpret"))
def gather_page_blocks(pool, src_block_ids, block_pages: int = 1,
                       interpret: bool = False):
    """pool: (P, page, D); src_block_ids: (M,) int32 — id of each source
    block of ``block_pages`` consecutive pages.  Returns
    (M * block_pages, page, D) gathered pages.

    With block_pages > 1 the DMA granularity grows accordingly — the
    kernel issues M DMAs instead of M · block_pages (the coalescing win
    measured in benchmarks/bench_kernels.py).
    """
    p_total, page, d = pool.shape
    m = src_block_ids.shape[0]
    assert p_total % block_pages == 0
    pool_b = pool.reshape(p_total // block_pages, block_pages * page, d)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[pl.BlockSpec((None, block_pages * page, d),
                                   lambda i, ids: (ids[i], 0, 0))],
            out_specs=pl.BlockSpec((None, block_pages * page, d),
                                   lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, block_pages * page, d),
                                       pool.dtype),
        interpret=interpret,
    )(src_block_ids.astype(jnp.int32), pool_b)
    return out.reshape(m * block_pages, page, d)


def _move_kernel(src_ref, dst_ref, page_ref, out_ref):
    out_ref[...] = page_ref[...]


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("interpret",))
def move_pages(pool, src, dst, interpret: bool = False):
    """pool: (L, 2, P, page, kvH, hd), donated; src, dst: (M,) int32.
    Returns the pool with page ``src[i]`` copied to page ``dst[i]`` in every
    layer's K and V plane, in place (the output aliases the pool).

    One grid step per move, one block of every plane's page through VMEM;
    the pipeline fetches step i+1's source while it writes step i's
    destination.  The moves must be as a stable pack gives them, both
    ascending and ``dst[i] < src[i]``: a destination that is another
    move's source then belongs to a later move, whose write starts after
    that source was read.  A move repeated at once (padding) is fetched and
    written once, as the pipeline skips an unchanged block index.
    """
    rest = (0,) * (pool.ndim - 3)
    block = pool.shape[:2] + (None,) + pool.shape[3:]
    return pl.pallas_call(
        _move_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(src.shape[0],),
            in_specs=[pl.BlockSpec(block,
                                   lambda i, s, d: (0, 0, s[i]) + rest)],
            out_specs=pl.BlockSpec(block,
                                   lambda i, s, d: (0, 0, d[i]) + rest),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), pool)
