"""Paged KV-cache manager — Scavenger+ on HBM pages (DESIGN.md §2).

Mapping of the paper's structures onto the serving tier:

  value store (vSSTs)   → per-layer K/V page pools in device memory
  index LSM-tree        → host page table (seq_id → page list)
  garbage               → pages of finished/evicted sequences
  hot/cold vSSTs        → ACTIVE vs FROZEN (paused/beam) sequence pools
  exposed-garbage ratio → free-list fragmentation of the pool
  GC (lazy read: only live data is read)
                        → live-page compaction that moves only the pages
                          whose index changes, every plane in one program

Compaction keeps live pages dense at the front of the pool so admission
of long prompts never fails on fragmentation; the scheduler triggers it
with the paper's pressure arithmetic (serving/scheduler.py).

The pool is updated in place: a decoded token's K/V row is written by
``kv_scatter_row`` and a compaction's moves by ``gc_compact.move_pages``,
both programs that take the pool donated.  An array taken from
``PagedKVCache.pool`` before ``write_token_kv`` or ``compact`` is deleted
by it; read the pool again afterwards.

Page tables handed to the attention kernel are padded to a multiple of
``PAGE_TABLE_BUCKET`` pages with unmapped (−1) columns, which the kernel
masks, so one attention program serves a range of sequence lengths.

While a profiler session is on, the write, attend and compact paths
record ``serve.cache.*`` spans and ``cache.*`` counters through
:mod:`repro.obs.spans`.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import ops
from ..kernels.gc_compact import move_pages
from ..models.config import ModelConfig
from ..obs import spans

PAGE_TABLE_BUCKET = 16     # page-table widths are multiples of this


@functools.partial(jax.jit, donate_argnums=0)
def kv_scatter_row(pool, where, k, v):
    """pool: (L, 2, P, page, kvH, hd), donated; where: (3,) int32, the
    layer, page and slot; k, v: (kvH, hd).  Returns the pool with that row
    of the layer's K and V planes set to ``k`` and ``v``, cast to the pool's
    dtype, in place (the output aliases the pool).  The position is traced,
    so one program serves every layer, page and slot."""
    row = jnp.stack([k, v]).astype(pool.dtype)[None, :, None, None]
    zero = jnp.zeros((), jnp.int32)
    return lax.dynamic_update_slice(
        pool, row, (where[0], zero, where[1], where[2], zero, zero))


@dataclasses.dataclass
class PagedCacheConfig:
    n_pages: int
    page_size: int = 16
    use_pallas: bool = True
    interpret: Optional[bool] = None   # None: ops.interpret_mode decides


class PagedKVCache:
    """Host-managed page table over device K/V pools for one layer stack."""

    def __init__(self, cfg: ModelConfig, pc: PagedCacheConfig) -> None:
        self.cfg = cfg
        self.pc = pc
        shape = (cfg.n_layers, 2, pc.n_pages, pc.page_size,
                 cfg.kv_heads, cfg.head_dim)
        self.pool = jnp.zeros(shape, cfg.compute_dtype)
        self.free: List[int] = list(range(pc.n_pages - 1, -1, -1))
        self.tables: Dict[int, List[int]] = {}      # seq -> page ids
        self.lengths: Dict[int, int] = {}
        self.frozen: Dict[int, bool] = {}           # cold sequences
        self.compactions = 0
        self.alloc_failures = 0

    # -- space accounting (paper eq. 5 analog) ---------------------------
    @property
    def used_pages(self) -> int:
        return sum(len(v) for v in self.tables.values())

    @property
    def free_pages(self) -> int:
        return len(self.free)

    def fragmentation(self) -> float:
        """Exposed-garbage analog: fraction of the *allocated prefix* of
        the pool that is free (holes blocking contiguous growth)."""
        if not self.tables:
            return 0.0
        hi = max((max(t) for t in self.tables.values() if t), default=-1)
        if hi < 0:
            return 0.0
        live = self.used_pages
        return 1.0 - live / (hi + 1)

    # -- allocation -------------------------------------------------------
    def add_sequence(self, seq_id: int, prompt_len: int) -> bool:
        n = -(-max(prompt_len, 1) // self.pc.page_size)
        if len(self.free) < n:
            self.alloc_failures += 1
            return False
        self.tables[seq_id] = [self.free.pop() for _ in range(n)]
        self.lengths[seq_id] = prompt_len
        self.frozen[seq_id] = False
        return True

    def append_token(self, seq_id: int) -> bool:
        """Reserve room for one more token; grabs a new page on boundary."""
        ln = self.lengths[seq_id]
        if ln % self.pc.page_size == 0 and ln > 0 or \
                ln == self.pc.page_size * len(self.tables[seq_id]):
            if not self.free:
                self.alloc_failures += 1
                return False
            self.tables[seq_id].append(self.free.pop())
        self.lengths[seq_id] = ln + 1
        return True

    def finish_sequence(self, seq_id: int) -> None:
        """Completion turns the sequence's pages into reclaimable garbage
        (freed immediately — 'exposed'); fragmentation may remain."""
        for p in self.tables.pop(seq_id, []):
            self.free.append(p)
        self.lengths.pop(seq_id, None)
        self.frozen.pop(seq_id, None)

    def freeze(self, seq_id: int, frozen: bool = True) -> None:
        self.frozen[seq_id] = frozen

    # -- device-side views -------------------------------------------------
    def page_table_array(self, seq_ids: List[int]) -> Tuple[jax.Array,
                                                            jax.Array]:
        """The sequences' page tables, padded with −1 to a width that is a
        multiple of ``PAGE_TABLE_BUCKET`` pages (at most ``n_pages``), and
        their lengths."""
        with spans.span("serve.cache.page_table"):
            max_pages = max((len(self.tables[s]) for s in seq_ids),
                            default=1)
            width = min(-(-max_pages // PAGE_TABLE_BUCKET)
                        * PAGE_TABLE_BUCKET, self.pc.n_pages)
            pt = np.full((len(seq_ids), width), -1, np.int32)
            ln = np.zeros((len(seq_ids),), np.int32)
            for i, s in enumerate(seq_ids):
                pages = self.tables[s]
                pt[i, :len(pages)] = pages
                ln[i] = self.lengths[s]
            spans.count("cache.page_table.pad_pages",
                        (width - max_pages) * len(seq_ids))
            return jnp.asarray(pt), jnp.asarray(ln)

    def write_token_kv(self, layer: int, seq_id: int, k, v) -> None:
        """Write one token's K/V (kvH, hd) into the page pool, at the
        sequence's last position, by one program that updates the pool in
        place (``kv_scatter_row``).  The pool is donated to it: an array
        taken from ``self.pool`` before the call is deleted."""
        pos = self.lengths[seq_id] - 1
        page = self.tables[seq_id][pos // self.pc.page_size]
        slot = pos % self.pc.page_size
        on = spans.on()
        with (spans.Span("serve.cache.kv_write", seq=seq_id, page=page,
                         slot=slot) if on else spans.OFF):
            self.pool = kv_scatter_row(
                self.pool, np.array((layer, page, slot), np.int32), k, v)
        if on:
            spans.count("cache.kv_rows_written", 2)

    def attend(self, layer: int, seq_ids: List[int], q) -> jax.Array:
        """Decode attention for the given sequences via the paged kernel.
        q: (B, H, hd) → (B, H, hd)."""
        with spans.span("serve.cache.attend"):
            pt, ln = self.page_table_array(seq_ids)
            return ops.decode_attention(
                q, self.pool[layer, 0], self.pool[layer, 1], pt, ln,
                use_pallas=self.pc.use_pallas, interpret=self.pc.interpret)

    # -- GC: live-page compaction (paper III-B.4 on HBM) ------------------
    def compact(self) -> int:
        """Pack live pages to the front of the pool, stably: the i-th live
        page (in page order) goes to slot i and the free list becomes every
        slot after the last live one.

        Only the live pages whose index changes are read and written, in
        every layer's K and V plane, by one program that updates the pool in
        place (``kernels.gc_compact.move_pages``).  The pool is donated to
        it: an array taken from ``self.pool`` before the call is deleted.
        Returns the pages relocated: live pages whose index changed, times
        the planes (layers x K/V) that hold them."""
        planes = self.cfg.n_layers * 2
        with spans.span("serve.cache.compact"):
            with spans.span("serve.cache.compact.plan"):
                valid = np.zeros(self.pc.n_pages, bool)
                valid[np.fromiter(itertools.chain.from_iterable(
                    self.tables.values()), np.intp)] = True
                live = np.flatnonzero(valid)
                n_live = len(live)
                new_index = np.full(self.pc.n_pages, -1, np.int32)
                new_index[live] = np.arange(n_live, dtype=np.int32)
                src = live[new_index[live] != live].astype(np.int32)
                dst = new_index[src]
            # ``copied`` counts the pages the mover's index asks for
            with spans.span("serve.cache.compact.move"):
                copied = 0
                if len(src):
                    n = _bucket(len(src), self.pc.n_pages)
                    # pad with the last move, which rewrites the same value;
                    # an identity move on a page that another move writes
                    # would race that write
                    pad = n - len(src)
                    self.pool = move_pages(
                        self.pool, jnp.asarray(np.pad(src, (0, pad), "edge")),
                        jnp.asarray(np.pad(dst, (0, pad), "edge")),
                        interpret=ops.interpret_mode(self.pc.interpret))
                    copied = n * planes
            with spans.span("serve.cache.compact.tables"):
                for s, pages in self.tables.items():
                    self.tables[s] = new_index[pages].tolist()
                self.free = list(range(self.pc.n_pages - 1, n_live - 1, -1))
        self.compactions += 1
        spans.count("cache.compact.pages_live", n_live)
        spans.count("cache.compact.pages_relocated", len(src) * planes)
        spans.count("cache.compact.pages_copied", copied)
        return len(src) * planes


def _bucket(moves: int, n_pages: int) -> int:
    """The mover's index length for ``moves`` moves: the next power of two,
    at least 16 and at most ``n_pages``, so one program serves a range of
    compaction sizes."""
    return min(max(16, 1 << (moves - 1).bit_length()), n_pages)
