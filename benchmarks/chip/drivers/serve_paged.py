"""Driver ``serve_paged``: continuous batching over the paged KV cache, with
page GC, through ``ServeLoop.step``.

The decode step is the serving entry's own (``repro.launch.serve``): the
layer-0 projections of a seeded input, ``PagedKVCache.write_token_kv`` for
each sequence, then ``PagedKVCache.attend`` through the paged-attention
kernel; each iteration ends by reading the step's output back to the host,
as a server hands tokens out.  The backlog never empties: the mix's
generator tops the queue up before every iteration.

Set-up replays the window's page schedule on the real pool before the
window: the schedule depends only on the request lengths, so the replay
meets every attention shape (batch, page-table width) and every compaction
size the window will, and compiles each once.  Its first iterations run in
full and time a decode iteration; the replay then runs far enough past
what ``--seconds`` can reach at that pace.

The window's pool starts filled from the seed, not zeroed: this path
has no prefill, so a sequence's prompt rows are whatever its pages held.
Filled, every row differs from every other, and a page that a compaction
loses, moves to the wrong place or overwrites changes the attention of
the decode steps on one side of that compaction.

After the window: the device's peak memory is read, the pool and weights
are dropped, and the sequences still active at the close are checked
against ``refs/paged_decode``: every decode step they took part in
(attention output, through the kernel, the page tables and the K/V the
writes put there, across any compaction of their lifetime), and every K/V
row their decode steps wrote.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import traffic as traffic_gen
import weights
from refs import paged_decode as ref

QUEUE_DEPTH = 16          # requests kept waiting behind the batch
FULL_REPLAY_ITERS = 48    # replay iterations run in full, to time one


class _Serve:
    """One pool, one loop and one request stream: the window's, or the
    replay's (the same stream from the same seed)."""

    def __init__(self, cfg, mix, seed, lp0, x_key, record: bool):
        from repro.serving import (PagedCacheConfig, PagedKVCache,
                                   ServeConfig, ServeLoop)
        b = mix["batching"]
        self.cfg = cfg
        self.cache = PagedKVCache(cfg, PagedCacheConfig(
            n_pages=mix["pool"]["n_pages"],
            page_size=mix["pool"]["page_size"]))
        self.loop = ServeLoop(cfg, self.cache, ServeConfig(
            max_batch=b["max_batch"], frag_threshold=b["frag_threshold"],
            min_decode_between_compactions=b["min_decode_between_compactions"]
        ))
        self.stream = traffic_gen.requests(mix, seed)
        self.next_rid = 0
        self.lp0, self.x_key = lp0, x_key
        self.record = record
        self.steps: List[Dict] = []      # one per decode iteration
        self.admitted: set = set()
        self.failed: set = set()
        self.skip_shapes = None          # replay: set of seen shapes

    def top_up(self) -> None:
        from repro.serving import Request
        while len(self.loop.queue) < QUEUE_DEPTH:
            p, o = next(self.stream)
            self.loop.submit(Request(rid=self.next_rid, prompt_len=p,
                                     max_new_tokens=o))
            self.next_rid += 1

    def decode(self, seq_ids) -> None:
        """``repro.launch.serve``'s decode step, for one iteration."""
        cache, t = self.cache, self.loop.decode_steps
        lengths = [cache.lengths[s] for s in seq_ids]
        self.admitted.update(self.loop.active)
        self.failed.update(set(self.loop.active) - set(seq_ids))
        write = True
        if self.skip_shapes is not None:
            # replay: run each new attention shape once; the writes only
            # for a new batch size (their programs do not see the pages)
            shape = (len(seq_ids), max(len(cache.tables[s])
                                       for s in seq_ids))
            if shape in self.skip_shapes:
                return
            write = not any(b == shape[0] for b, _ in self.skip_shapes)
            self.skip_shapes.add(shape)
        with jax.profiler.TraceAnnotation("serve.project"):
            x = jax.random.normal(jax.random.fold_in(self.x_key, t),
                                  (len(seq_ids), 1, self.cfg.d_model),
                                  jnp.float32)
            k = jnp.einsum("bsd,dhk->bshk", x, self.lp0["wk"])[:, 0]
            v = jnp.einsum("bsd,dhk->bshk", x, self.lp0["wv"])[:, 0]
        with jax.profiler.TraceAnnotation("serve.kv_write"):
            for i, s in enumerate(seq_ids if write else ()):
                cache.write_token_kv(0, s, k[i], v[i])
        with jax.profiler.TraceAnnotation("serve.attend"):
            q = jnp.einsum("bsd,dhk->bshk", x, self.lp0["wq"])[:, 0]
            out = cache.attend(0, seq_ids, q)
        with jax.profiler.TraceAnnotation("serve.readback"):
            out = np.asarray(out)
        if self.record:
            self.steps.append({"t": t, "seqs": list(seq_ids),
                               "lengths": lengths, "out": out,
                               "pages": max(len(cache.tables[s])
                                            for s in seq_ids)})

    def step(self) -> int:
        """One engine iteration; returns 1 if it compacted."""
        self.top_up()
        with jax.profiler.TraceAnnotation("serve.step"):
            return int(self.loop.step(self.decode)["kind"])


def _replay(ctx, cfg, mix, lp0, x_key) -> Dict:
    """Run the window's schedule ahead of it on a pool of its own, compiling
    every shape it meets; returns what it saw."""
    sv = _Serve(cfg, mix, ctx.seed, lp0, x_key, record=False)
    durations = []
    for _ in range(FULL_REPLAY_ITERS):
        c0, t0 = ctx.meter.count + ctx.meter.hits, time.perf_counter()
        kind = sv.step()
        if kind == 0 and ctx.meter.count + ctx.meter.hits == c0:
            durations.append(time.perf_counter() - t0)
    per_iter = float(np.median(durations)) if durations else 1.0
    horizon = max(FULL_REPLAY_ITERS, int(
        mix["replay_margin"] * ctx.seconds / per_iter))
    sv.skip_shapes = set()
    compactions = sv.loop.compaction_steps
    for _ in range(horizon - FULL_REPLAY_ITERS):
        sv.step()
    jax.block_until_ready(sv.cache.pool)
    info = {"replay_iterations": horizon, "replay_iter_s": per_iter,
            "replay_compactions": sv.loop.compaction_steps - compactions,
            "replay_shapes": len(sv.skip_shapes)}
    del sv
    return info


@functools.partial(jax.jit, donate_argnums=0)
def _filled(pool, key):
    """The pool, every element set from the seed (uniform, unit variance,
    as the rows the decode steps write), in place: a hash of the
    element's index, so no temporary as large as the pool is made."""
    idx = jnp.zeros(pool.shape, jnp.uint32)
    for d, n in enumerate(pool.shape):
        idx = idx * jnp.uint32(n) + lax.broadcasted_iota(jnp.uint32,
                                                         pool.shape, d)
    h = idx * jnp.uint32(0x9E3779B1) ^ key[0]
    for shift, mul in ((16, 0x85EBCA6B), (13, 0xC2B2AE35), (16, 0x27D4EB2F)):
        h = (h ^ (h >> shift)) * jnp.uint32(mul) ^ key[1]
    u = (h >> 8).astype(jnp.float32) * 2.0 ** -24
    return ((2.0 * u - 1.0) * 3.0 ** 0.5).astype(pool.dtype)


def _layer0(cfg, key) -> Dict:
    """Layer-0 q/k/v weights, as the reference makes them (no program)."""
    shape = (cfg["n_layers"], cfg["d_model"], cfg["n_heads"],
             cfg["head_dim"])
    kv = (cfg["n_layers"], cfg["d_model"], cfg["kv_heads"], cfg["head_dim"])
    return {n: weights.leaf(("layers", "attn", n), s, jnp.float32, key)[0]
            for n, s in (("wq", shape), ("wk", kv), ("wv", kv))}


def _checked(sv: _Serve) -> List[Dict]:
    """The sequences active at the close: each one's decode steps, and its
    K/V rows as the cache holds them, read back to the host."""
    ps, out = sv.cache.pc.page_size, []
    pool0 = np.asarray(sv.cache.pool[0])          # layer 0's K and V planes
    for s in sv.loop.active:
        pages, length = list(sv.cache.tables[s]), sv.cache.lengths[s]
        rows = pool0[:, pages].reshape(2, len(pages) * ps,
                                       *pool0.shape[-2:])[:, :length]
        mine = [(st["t"], st["seqs"].index(s)) for st in sv.steps
                if s in st["seqs"]]
        if mine:
            out.append({"rows": rows.astype(np.float32), "steps": mine})
    return out


def _pad(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    return np.concatenate([a, np.full((n - len(a),) + a.shape[1:], fill,
                                      a.dtype)])


def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ||got − want||₂ / ||want||₂ over the leading axis."""
    got, want = got.astype(np.float64), want.astype(np.float64)
    axes = tuple(range(1, want.ndim))
    num = np.sqrt(np.sum(np.square(got - want), axis=axes))
    den = np.sqrt(np.sum(np.square(want), axis=axes))
    return float(np.max(num / np.maximum(den, 1e-30)))


def _check(ctx, seqs: List[Dict], steps: Dict[int, Dict], cfgj: Dict,
           mix: Dict):
    """Compare the checked sequences with the reference.  Shapes are padded
    to buckets so the reference compiles a handful of programs."""
    x_key = jax.random.fold_in(weights.seed_key(ctx.seed), 1)
    w = jax.jit(lambda k: _layer0(cfgj, k))(weights.seed_key(ctx.seed))
    lim = mix["limits"]
    kv_dtype = lim["control_kv_dtype"] if ctx.control else None
    proj3 = jax.jit(lambda x, w: [ref.project(x[:, 0], w[n])
                                  for n in ("wq", "wk", "wv")])
    attend = jax.jit(ref.attend_steps, static_argnums=(4,))
    proj = {}
    for t in sorted({t for sq in seqs for t, _ in sq["steps"]}):
        x = jax.random.normal(jax.random.fold_in(x_key, t),
                              (len(steps[t]["seqs"]), 1, cfgj["d_model"]),
                              jnp.float32)
        proj[t] = [np.asarray(a) for a in proj3(x, w)]
    attn_errs, kv_errs, nonfinite, n_steps = [], [], 0, 0
    for sq in seqs:
        mine = sq["steps"]
        lens = np.asarray([steps[t]["lengths"][i] for t, i in mine])
        written = lens - 1
        q, k_want, v_want = (np.stack([proj[t][j][i] for t, i in mine])
                             for j in range(3))
        k_rows, v_rows = sq["rows"]
        k_got, v_got = k_rows[written], v_rows[written]
        if ctx.control:
            k_got, v_got = (np.asarray(jnp.asarray(a).astype(kv_dtype)
                                       .astype(jnp.float32))
                            for a in (k_want, v_want))
        kv_errs += [_rel_err(k_got, k_want), _rel_err(v_got, v_want)]
        # the reference's rows: what each decode step should have written,
        # and the cache's own rows at the close where no step wrote (the
        # prompt, filled from the seed: a page moved wrongly by a
        # compaction reads differently before it and after)
        k_ref, v_ref = k_rows.copy(), v_rows.copy()
        k_ref[written], v_ref[written] = k_want, v_want
        nt = -(-len(mine) // 64) * 64
        nl = -(-len(k_ref) // 512) * 512
        args = (_pad(q, nt), _pad(k_ref, nl), _pad(v_ref, nl),
                _pad(lens, nt, 1))
        want = np.asarray(attend(*args, None))[:len(mine)]
        if ctx.control:
            got = np.asarray(attend(*args, kv_dtype))[:len(mine)]
        else:
            got = np.stack([steps[t]["out"][i] for t, i in mine])
        nonfinite += int((~np.isfinite(got)).sum())
        attn_errs.append(_rel_err(got, want))
        n_steps += len(mine)
    checks = {
        "attn_rel_err": {"value": max(attn_errs, default=float("nan")),
                         "limit": lim["attn_rel_err"]},
        "kv_rel_err": {"value": max(kv_errs, default=float("nan")),
                       "limit": lim["kv_rel_err"]},
        "nonfinite": {"value": nonfinite, "limit": 0},
        "checked_steps": {"value": n_steps, "limit": lim["min_checked"]},
    }
    ok = (n_steps >= lim["min_checked"] and nonfinite == 0
          and checks["attn_rel_err"]["value"] <= lim["attn_rel_err"]
          and checks["kv_rel_err"]["value"] <= lim["kv_rel_err"])
    return ok, checks


def run(ctx) -> Dict:
    from repro.configs import get_config
    from repro.models import get_model
    cfgj, mix = ctx.config(), ctx.mix()
    cfg = get_config(cfgj["model"], smoke=ctx.smoke)
    key = weights.seed_key(ctx.seed)
    # the whole model, in the type it is served in, as the entry holds it
    params = weights.make(get_model(cfg).init(cfg, abstract=True), key)
    lp0 = jax.tree.map(lambda a: a[0], params["layers"])["attn"]
    x_key = jax.random.fold_in(key, 1)
    t0, c0 = time.perf_counter(), ctx.meter.count
    info = _replay(ctx, cfg, mix, lp0, x_key)
    info.update(replay_s=time.perf_counter() - t0,
                replay_compiles=ctx.meter.count - c0,
                cache_hits=ctx.meter.hits, compile_s=ctx.meter.secs)

    sv = _Serve(cfg, mix, ctx.seed, lp0, x_key, record=True)
    sv.cache.pool = _filled(sv.cache.pool, jax.random.fold_in(key, 2))
    jax.block_until_ready(sv.cache.pool)
    ends: List[float] = []
    kinds: List[int] = []
    starts: List[float] = []
    ctx.start_window()
    while ctx.window_open():
        starts.append(time.perf_counter())
        kinds.append(sv.step())
        jax.block_until_ready(sv.cache.pool)
        ends.append(time.perf_counter())
    window_s = ctx.end_window()

    # inter-token gaps: each decode iteration hands every sequence in it
    # one token at the iteration's end
    last: Dict[int, float] = {}
    gaps: List[float] = []
    decode_ends = [e for e, k in zip(ends, kinds) if k == 0]
    tokens = 0
    for st, end in zip(sv.steps, decode_ends):
        tokens += len(st["seqs"])
        for s in st["seqs"]:
            if s in last:
                gaps.append(end - last[s])
            last[s] = end
    itl_p95 = (float(np.percentile(np.asarray(gaps), 95, method="linear"))
               if gaps else float("nan"))
    record = {
        "window_s": window_s, "tokens": tokens,
        "iterations": [{"kind": k, "start": a - ctx.t_window,
                        "end": b - ctx.t_window}
                       for k, a, b in zip(kinds, starts, ends)],
        "decode_steps": [{"batch": len(st["seqs"]),
                          "lengths": st["lengths"], "pages": st["pages"]}
                         for st in sv.steps],
        "model": {k: cfgj[k] for k in ("d_model", "n_heads", "kv_heads",
                                       "head_dim")},
        "kv_bytes": 2, "weight_bytes": 4, **info,
    }
    attempted = len(sv.admitted)
    failed = len(sv.failed)
    seqs = _checked(sv)
    steps = {st["t"]: st for st in sv.steps}
    del params, lp0, sv        # the program's state goes before the check
    t0 = time.perf_counter()
    ok, checks = _check(ctx, seqs, steps, cfgj, mix)
    record["check_s"] = time.perf_counter() - t0
    return {"correct": ok and failed == 0, "attempted": attempted,
            "failed": failed, "record": record, "checks": checks,
            "end_to_end": {"serve_tok_s": tokens / window_s,
                           "itl_p95_ms": itl_p95 * 1e3}}
