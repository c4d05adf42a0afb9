"""Serving: paged cache correctness (attend == dense reference), K/V row
writes in place in the donated pool through one program, bucketed
page-table widths that leave attention unchanged, GC compaction preserves
live data in every plane as a stable pack, one mover program per bucket of
compaction sizes, scheduler completes all requests."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.gc_compact import move_pages
from repro.kernels.paged_attention import paged_attention
from repro.obs import spans
from repro.serving import (PagedCacheConfig, PagedKVCache, Request,
                           ServeConfig, ServeLoop)
from repro.serving.kvcache import PAGE_TABLE_BUCKET, kv_scatter_row


def _mk(n_pages=32, page_size=4):
    cfg = get_config("olmo-1b", smoke=True)
    return cfg, PagedKVCache(cfg, PagedCacheConfig(
        n_pages=n_pages, page_size=page_size, interpret=True))


def test_paged_attend_matches_dense():
    cfg, cache = _mk()
    rng = np.random.default_rng(0)
    assert cache.add_sequence(1, 0)
    kvs = []
    for t in range(7):
        cache.lengths[1] = t       # append_token path
        assert cache.append_token(1)
        k = jnp.asarray(rng.normal(size=(cfg.kv_heads, cfg.head_dim)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(cfg.kv_heads, cfg.head_dim)),
                        jnp.float32)
        cache.write_token_kv(0, 1, k, v)
        kvs.append((k, v))
    q = jnp.asarray(rng.normal(size=(1, cfg.n_heads, cfg.head_dim)),
                    jnp.float32)
    out = cache.attend(0, [1], q)
    # dense reference over the same (bf16-cast) cache lines
    ks = jnp.stack([k for k, _ in kvs])[None].astype(cache.pool.dtype) \
        .astype(jnp.float32)
    vs = jnp.stack([v for _, v in kvs])[None].astype(cache.pool.dtype) \
        .astype(jnp.float32)
    from repro.kernels.ref import flash_attention_ref
    want = flash_attention_ref(q[:, None], ks, vs, causal=False)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_compaction_preserves_live_kv():
    cfg, cache = _mk(n_pages=24, page_size=4)
    rng = np.random.default_rng(1)
    # three sequences; middle one finishes → holes
    for sid, n_tok in [(1, 9), (2, 6), (3, 10)]:
        assert cache.add_sequence(sid, 0)
        for t in range(n_tok):
            assert cache.append_token(sid)
            k = jnp.asarray(rng.normal(size=(cfg.kv_heads, cfg.head_dim)),
                            jnp.float32)
            cache.write_token_kv(0, sid, k, k * 2)
    q = jnp.asarray(rng.normal(size=(2, cfg.n_heads, cfg.head_dim)),
                    jnp.float32)
    before = cache.attend(0, [1, 3], q)
    cache.finish_sequence(2)
    frag_before = cache.fragmentation()
    relocated = cache.compact()
    # sequence 3's three pages move down into sequence 2's two holes, in
    # every layer's K and V plane
    assert relocated == 3 * cfg.n_layers * 2
    after = cache.attend(0, [1, 3], q)
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               atol=1e-6)
    assert cache.fragmentation() <= frag_before


def _filled(n_pages=12, page_size=4):
    """A cache whose pool is filled from a seed, so that a write that
    lands anywhere else shows."""
    cfg, cache = _mk(n_pages=n_pages, page_size=page_size)
    cache.pool = jax.random.normal(jax.random.PRNGKey(9), cache.pool.shape,
                                   jnp.float32).astype(cache.pool.dtype)
    return cfg, cache


def test_kv_writes_match_a_numpy_model_row_for_row():
    """Writes across layers, pages, slots and both planes leave the pool
    equal to the same writes made on a NumPy copy, every other row as it
    was."""
    cfg, cache = _filled()
    want = np.asarray(cache.pool).copy()
    rng = np.random.default_rng(4)
    for sid, prompt in ((1, 3), (2, 0), (3, 6)):
        assert cache.add_sequence(sid, prompt)
    for t in range(11):
        for sid in (1, 2, 3):
            assert cache.append_token(sid)
            pos = cache.lengths[sid] - 1
            page = cache.tables[sid][pos // cache.pc.page_size]
            slot = pos % cache.pc.page_size
            layer = (t + sid) % cfg.n_layers
            k, v = (rng.normal(size=(cfg.kv_heads, cfg.head_dim))
                    .astype(np.float32) for _ in range(2))
            cache.write_token_kv(layer, sid, jnp.asarray(k), jnp.asarray(v))
            want[layer, 0, page, slot] = k.astype(want.dtype)
            want[layer, 1, page, slot] = v.astype(want.dtype)
    np.testing.assert_array_equal(np.asarray(cache.pool).view(np.uint16),
                                  want.view(np.uint16))


def test_kv_writes_at_every_position_share_one_program():
    kv_scatter_row.clear_cache()
    cfg, cache = _filled()
    assert cache.add_sequence(1, 0)
    k = jnp.ones((cfg.kv_heads, cfg.head_dim), jnp.float32)
    for t in range(9):                  # three pages, every slot
        assert cache.append_token(1)
        cache.write_token_kv(t % cfg.n_layers, 1, k * t, -k * t)
    assert kv_scatter_row._cache_size() == 1


def _honours_donation() -> bool:
    x = jnp.zeros((8,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


def test_kv_write_donates_the_pool():
    cfg, cache = _filled()
    assert cache.add_sequence(1, 1)
    before = cache.pool
    k = jnp.ones((cfg.kv_heads, cfg.head_dim), jnp.float32)
    cache.write_token_kv(0, 1, k, k)
    assert cache.pool is not before
    if _honours_donation():
        assert before.is_deleted()


@pytest.mark.parametrize("n_pages", [40, 64])
def test_bucketed_page_table_leaves_attention_unchanged(n_pages, tmp_path):
    """``attend`` over the bucketed table equals the kernel over the exact
    one, bit for bit; every page count from 1 to ``n_pages`` takes one of
    at most ceil(n_pages / 16) widths; the padding is counted."""
    cfg, cache = _filled(n_pages=n_pages, page_size=2)
    for sid, pages in ((0, 3), (1, 17), (2, 5)):
        assert cache.add_sequence(sid, 2 * pages - sid % 2)
    seqs = [0, 1, 2]
    q = jax.random.normal(jax.random.PRNGKey(3),
                          (3, cfg.n_heads, cfg.head_dim), jnp.float32)
    pt, ln = cache.page_table_array(seqs)
    assert pt.shape == (3, 2 * PAGE_TABLE_BUCKET)
    exact = np.full((3, 17), -1, np.int32)
    for i, s in enumerate(seqs):
        exact[i, :len(cache.tables[s])] = cache.tables[s]
    want = paged_attention(q, cache.pool[0, 0], cache.pool[0, 1],
                           jnp.asarray(exact), ln, interpret=True)
    got = cache.attend(0, seqs, q)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    for s in seqs:
        cache.finish_sequence(s)
    widths, padded = set(), 0
    with jax.profiler.trace(str(tmp_path)):
        for n in range(1, n_pages + 1):
            assert cache.add_sequence(n, 2 * n)
            width = cache.page_table_array([n])[0].shape[1]
            assert n <= width <= n_pages
            assert width % PAGE_TABLE_BUCKET == 0 or width == n_pages
            widths.add(width)
            padded += width - n
            cache.finish_sequence(n)
        counted = spans.snapshot()["counters"]["cache.page_table.pad_pages"]
    assert len(widths) <= math.ceil(n_pages / PAGE_TABLE_BUCKET)
    assert counted == padded > 0


def _holed(page_counts, finished, use_pallas=True):
    """A 64-page cache whose pool is filled from a seed, holding one
    sequence of each page count (allocated in order), less the finished."""
    cfg = get_config("olmo-1b", smoke=True)
    cache = PagedKVCache(cfg, PagedCacheConfig(
        n_pages=64, page_size=2, use_pallas=use_pallas, interpret=True))
    cache.pool = jax.random.normal(jax.random.PRNGKey(5), cache.pool.shape,
                                   jnp.float32).astype(cache.pool.dtype)
    for s, n in enumerate(page_counts):
        assert cache.add_sequence(s, n * cache.pc.page_size)
    for s in finished:
        cache.finish_sequence(s)
    return cfg, cache


# case -> (pages of each sequence, sequences finished, pages that move)
GC_CASES = {
    # 2->0, 3->1, 4->2: the first move's source is the third's destination
    "3_moves": ((2, 3), (0,), 3),
    # two holes: 1,2 -> 0,1 and 4,5,6 -> 2,3,4
    "5_moves": ((1, 2, 1, 3), (0, 2), 5),
    # past the smallest bucket (16)
    "17_moves": ((4, 17), (0,), 17),
    # the hole is at the end: nothing moves
    "0_moves": ((3, 2), (1,), 0),
}


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("case", sorted(GC_CASES))
def test_compaction_is_a_stable_pack_in_every_plane(case, use_pallas):
    """Every layer's K and V plane, the tables and the free list after
    ``compact()`` against a NumPy stable pack of the pool before it."""
    pages, finished, moves = GC_CASES[case]
    cfg, cache = _holed(pages, finished, use_pallas)
    before = np.asarray(cache.pool).view(np.uint16)
    tables = {s: list(t) for s, t in cache.tables.items()}
    relocated = cache.compact()

    live = sorted(p for t in tables.values() for p in t)
    rank = {p: i for i, p in enumerate(live)}
    assert sum(rank[p] != p for p in live) == moves
    assert relocated == moves * cfg.n_layers * 2
    assert cache.tables == {s: [rank[p] for p in t]
                            for s, t in tables.items()}
    assert cache.free == list(range(cache.pc.n_pages - 1, len(live) - 1, -1))
    after = np.asarray(cache.pool).view(np.uint16)
    np.testing.assert_array_equal(after[:, :, :len(live)],
                                  before[:, :, live])


def test_compactions_in_one_bucket_share_one_mover_program():
    move_pages.clear_cache()
    for moves in (3, 5, 9, 16):         # all padded to 16 moves
        _, cache = _holed((2, moves), (0,))
        cache.compact()
    assert move_pages._cache_size() == 1
    _, cache = _holed((2, 17), (0,))     # 32
    cache.compact()
    assert move_pages._cache_size() == 2


def test_scheduler_completes_all_requests_with_compaction():
    cfg, cache = _mk(n_pages=48, page_size=4)
    loop = ServeLoop(cfg, cache, ServeConfig(
        max_batch=4, frag_threshold=0.15,
        min_decode_between_compactions=2))
    rng = np.random.default_rng(2)
    for i in range(10):
        loop.submit(Request(rid=i, prompt_len=int(rng.integers(4, 16)),
                            max_new_tokens=int(rng.integers(2, 8))))

    def decode_fn(seq_ids):
        for s in seq_ids:
            k = jnp.ones((cfg.kv_heads, cfg.head_dim)) * 0.1
            cache.write_token_kv(0, s, k, k)

    loop.run(decode_fn, max_steps=400)
    assert len(loop.done) == 10
    assert not loop.active and not loop.queue


def test_pressures_trigger_compaction_under_fragmentation():
    cfg, cache = _mk(n_pages=16, page_size=4)
    loop = ServeLoop(cfg, cache, ServeConfig(
        max_batch=8, frag_threshold=0.1,
        min_decode_between_compactions=0))
    # allocate interleaved sequences then finish every other one
    for sid in range(6):
        assert cache.add_sequence(sid, 8)
    for sid in range(0, 6, 2):
        cache.finish_sequence(sid)
    assert cache.fragmentation() > 0.1
    assert loop.should_compact()


def test_serve_paged_example_runs(capsys):
    """The shipped example serves its 16 requests and prints its summary."""
    import runpy
    from pathlib import Path
    runpy.run_path(str(Path(__file__).resolve().parents[1] / "examples"
                       / "serve_paged.py"), run_name="__main__")
    assert "completed=16" in capsys.readouterr().out
