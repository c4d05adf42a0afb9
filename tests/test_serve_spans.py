"""The host spans and counters of the serving tier (``repro.obs.spans``):
nothing recorded with the profiler off; under a profiler session, every
span with its parent, counters that agree with the page tables, the same
spans nested the same way in the profiler's own trace, and a fresh start
with each session.  SMOKE widths, Pallas kernels in interpret mode."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.obs import spans
from repro.serving import (PagedCacheConfig, PagedKVCache, Request,
                           ServeConfig, ServeLoop)

MAX_BATCH = 4


def _mover_index(moves, n_pages):
    """The length of the mover's index for a compaction of ``moves`` pages:
    the next power of two, at least 16 and at most the pool's pages."""
    if not moves:
        return 0
    n = 16
    while n < moves:
        n *= 2
    return min(n, n_pages)


# span -> the span it opens under, in a ServeLoop run
PARENTS = {
    "serve.sched.step": None,
    "serve.sched.decode": "serve.sched.step",
    "serve.cache.kv_write": "serve.sched.decode",
    "serve.cache.attend": "serve.sched.decode",
    "serve.cache.page_table": "serve.cache.attend",
    "serve.cache.compact": "serve.sched.step",
    "serve.cache.compact.plan": "serve.cache.compact",
    "serve.cache.compact.move": "serve.cache.compact",
    "serve.cache.compact.tables": "serve.cache.compact",
}


class _TabledCache(PagedKVCache):
    """Keeps, for each compaction, the page tables before and after."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.compacted = []

    def compact(self) -> int:
        before = {s: list(t) for s, t in self.tables.items()}
        relocated = super().compact()
        self.compacted.append((before, {s: list(t)
                                        for s, t in self.tables.items()},
                               relocated))
        return relocated


def _serve(n_requests=8):
    """A loop over a small pool that compacts; returns (loop, cache,
    tokens decoded)."""
    cfg = get_config("olmo-1b", smoke=True)
    cache = _TabledCache(cfg, PagedCacheConfig(n_pages=32, page_size=4,
                                               interpret=True))
    loop = ServeLoop(cfg, cache, ServeConfig(
        max_batch=MAX_BATCH, frag_threshold=0.1,
        min_decode_between_compactions=2))
    rng = np.random.default_rng(3)
    for i in range(n_requests):
        loop.submit(Request(rid=i, prompt_len=int(rng.integers(4, 12)),
                            max_new_tokens=int(rng.integers(2, 7))))
    decoded = []
    q = jnp.ones((MAX_BATCH, cfg.n_heads, cfg.head_dim), jnp.float32)

    def decode_fn(seq_ids):
        for s in seq_ids:
            k = jnp.full((cfg.kv_heads, cfg.head_dim), 0.1 * s, jnp.float32)
            cache.write_token_kv(0, s, k, k)
        # a fixed batch, so the attention compiles once
        ids = (seq_ids * MAX_BATCH)[:MAX_BATCH]
        cache.attend(0, ids, q)
        decoded.append(len(seq_ids))

    loop.run(decode_fn, max_steps=200)
    assert len(loop.done) == n_requests and cache.compactions >= 2
    return loop, cache, sum(decoded)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        loop, cache, tokens = _serve()
    return {"snap": spans.snapshot(), "loop": loop, "cache": cache,
            "tokens": tokens, "logdir": logdir}


def test_profiler_off_records_nothing():
    spans.reset()
    assert not spans.on()
    assert spans.span("serve.sched.step") is spans.OFF
    assert spans.span("serve.cache.kv_write", seq=1) is spans.OFF
    spans.count("sched.compactions")
    _serve()
    assert spans.snapshot() == {"spans": {}, "counters": {}}


def test_traced_run_records_every_span_under_its_parent(traced):
    snap = traced["snap"]["spans"]
    assert set(snap) == set(PARENTS)
    for name, parent in PARENTS.items():
        s = snap[name]
        assert s["parents"] == [parent], (name, s["parents"])
        assert s["count"] > 0
        assert 0 <= s["self_ms"] <= s["total_ms"] * (1 + 1e-9), name
    loop, cache = traced["loop"], traced["cache"]
    assert snap["serve.sched.step"]["count"] == (loop.decode_steps
                                                 + loop.compaction_steps)
    assert snap["serve.cache.compact"]["count"] == cache.compactions
    assert snap["serve.cache.kv_write"]["count"] == traced["tokens"]
    # a parent's total covers its children's
    kids = sum(snap[n]["total_ms"] for n in PARENTS
               if PARENTS[n] == "serve.cache.compact")
    assert kids <= snap["serve.cache.compact"]["total_ms"]


def test_counters_agree_with_the_page_tables(traced):
    c = traced["snap"]["counters"]
    loop, cache, tokens = traced["loop"], traced["cache"], traced["tokens"]
    planes = cache.cfg.n_layers * 2
    relocated = live = copied = 0
    for before, after, returned in cache.compacted:
        assert before.keys() == after.keys()
        moved = sum(a != b for s in before
                    for a, b in zip(before[s], after[s]))
        relocated += moved * planes
        copied += _mover_index(moved, cache.pc.n_pages) * planes
        assert returned == moved * planes
        live += sum(len(t) for t in before.values())
        # live pages end dense at the front of the pool
        assert sorted(p for t in after.values() for p in t) == list(
            range(sum(len(t) for t in before.values())))
    assert relocated > 0
    assert c["cache.compact.pages_relocated"] == relocated
    assert c["cache.compact.pages_live"] == live
    assert c["cache.compact.pages_copied"] == copied
    assert c["cache.kv_rows_written"] == 2 * tokens
    assert c["sched.decode_seqs"] == tokens
    assert c["sched.decode_iterations"] == loop.decode_steps
    assert c["sched.batch_slots"] == loop.decode_steps * MAX_BATCH
    assert c["sched.compactions"] == loop.compaction_steps
    assert c["sched.admitted"] == len(loop.done)


def _host_events(logdir):
    """{name: [(start, end, line, stats)]} of the ``serve.*`` events on the
    host plane of the session's trace."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, line.name,
                         dict(e.stats)))
    return out


def test_profiler_trace_holds_the_same_spans_nested_the_same_way(traced):
    events = _host_events(traced["logdir"])
    snap = traced["snap"]["spans"]
    assert set(events) == set(snap)
    for name, evs in events.items():
        assert len(evs) == snap[name]["count"], name
        parent = PARENTS[name]
        if parent is None:
            continue
        for a, b, line, _ in evs:
            assert any(pa <= a and b <= pb and pl == line
                       for pa, pb, pl, _ in events[parent]), (name, a)
    stats = events["serve.cache.kv_write"][0][3]
    assert {"seq", "page", "slot"} <= set(stats)


def test_snapshot_reads_without_clearing_and_a_new_session_starts_afresh(
        traced, tmp_path):
    assert spans.snapshot() == traced["snap"]
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("serve.sched.step"):
            spans.count("sched.admitted", 3)
    assert spans.snapshot()["counters"] == {"sched.admitted": 3}
    assert list(spans.snapshot()["spans"]) == ["serve.sched.step"]
    assert spans.snapshot()["spans"]["serve.sched.step"]["count"] == 1


def test_train_step_names_its_scopes():
    from repro.launch.mesh import make_host_mesh
    from repro.train.step import build_train_step
    cfg = get_config("olmo-1b", smoke=True)
    fn, in_sh, out_sh, abstract = build_train_step(
        cfg, make_host_mesh(devices=1), 2, 16)
    text = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
        *abstract).as_text(debug_info=True)
    assert "train.loss" in text and "train.optimizer" in text


@pytest.mark.parametrize("use_pallas", [True, False])
def test_pages_copied_counts_what_the_gathers_ask_for(use_pallas, tmp_path):
    """The mover's index, every plane: the 4 pages that move, padded to the
    smallest bucket (16), whichever attention path the cache serves."""
    cfg = get_config("olmo-1b", smoke=True)
    cache = PagedKVCache(cfg, PagedCacheConfig(
        n_pages=16, page_size=4, use_pallas=use_pallas, interpret=True))
    for s, n in enumerate((9, 5, 13)):
        assert cache.add_sequence(s, n)
    cache.finish_sequence(1)
    live = sum(len(t) for t in cache.tables.values())
    with jax.profiler.trace(str(tmp_path)):
        cache.compact()
    c = spans.snapshot()["counters"]
    planes = cfg.n_layers * 2
    assert c["cache.compact.pages_relocated"] == 4 * planes
    assert c["cache.compact.pages_copied"] == 16 * planes
    assert c["cache.compact.pages_live"] == live
