"""Serving driver: continuous batching over the paged KV cache with
Scavenger+-style page GC, end to end at a model's full width.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
      --requests 24 [--pages 256] [--frag-threshold 0.2] [--smoke] [--check]

The driver reports the scheduling split between decode and compaction
iterations and the run-coalescing DMA statistics — the serving-tier
analog of the paper's Fig. 19/20 resource-efficiency story.

``--check`` verifies the served path as it runs: the first decode step's
kernel attention against the float32 jnp reference, every live page's
K/V bytes across each compaction, at least one compaction, and — where the
kernels run compiled — that their programs hold a ``tpu_custom_call``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..kernels import ops, ref
from ..kernels.gc_compact import gather_page_blocks
from ..kernels.paged_attention import paged_attention
from ..models import get_model
from ..serving import (PagedCacheConfig, PagedKVCache, Request, ServeConfig,
                       ServeLoop)
from .compile_cache import use_compile_cache


def _live_pages(cache: PagedKVCache) -> dict:
    """Each live sequence's K/V pages, every layer and plane, as raw bits."""
    return {s: np.asarray(cache.pool[:, :, jnp.asarray(pages)]
                          ).view(np.uint16)
            for s, pages in cache.tables.items() if pages}


class _CheckedCache(PagedKVCache):
    """A cache whose compactions prove that no live K/V byte changed."""

    def compact(self) -> int:
        before = _live_pages(self)
        dmas = super().compact()
        after = _live_pages(self)
        assert before.keys() == after.keys()
        for s in before:
            assert np.array_equal(before[s], after[s]), f"seq {s} changed"
        return dmas


def _check_attention(cache: PagedKVCache, seq_ids, q, out) -> float:
    """Kernel output vs ``ref.paged_attention_ref`` in float32."""
    pt, ln = cache.page_table_array(seq_ids)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(
            q.astype(f32), cache.pool[0, 0].astype(f32),
            cache.pool[0, 1].astype(f32), pt, ln)
    err = float(jnp.abs(out.astype(f32) - want).max())
    scale = float(jnp.abs(want).max())
    assert err <= 2e-2 * max(scale, 1.0), (err, scale)
    return err


def _kernel_hlo_ok(cache: PagedKVCache, seq_ids, q) -> bool:
    """Both served kernels compile to a Mosaic custom call."""
    pt, ln = cache.page_table_array(seq_ids)
    attn = paged_attention.lower(q, cache.pool[0, 0], cache.pool[0, 1],
                                 pt, ln).compile().as_text()
    plane = cache.pool[0, 0].reshape(cache.pc.n_pages, cache.pc.page_size,
                                     -1)
    gather = gather_page_blocks.lower(
        plane, jnp.zeros((1,), jnp.int32),
        block_pages=cache.pc.compact_block_pages).compile().as_text()
    return "tpu_custom_call" in attn and "tpu_custom_call" in gather


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--frag-threshold", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    params = jax.jit(lambda key: model.init(cfg, key))(
        jax.random.PRNGKey(args.seed))
    cache = (_CheckedCache if args.check else PagedKVCache)(
        cfg, PagedCacheConfig(n_pages=args.pages, page_size=args.page_size))
    loop = ServeLoop(cfg, cache, ServeConfig(
        max_batch=args.max_batch, frag_threshold=args.frag_threshold))

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        loop.submit(Request(rid=i, prompt_len=int(rng.integers(4, 32)),
                            max_new_tokens=int(rng.integers(4, 16))))

    # Layer-0 attention drives the paged pool; the remaining layers run
    # dense (full multi-layer paging wires each layer identically).
    lp0 = jax.tree.map(lambda a: a[0], params["layers"])["attn"]
    checks = []

    def decode_fn(seq_ids):
        x = jax.random.normal(jax.random.PRNGKey(loop.decode_steps),
                              (len(seq_ids), 1, cfg.d_model), jnp.float32)
        k = jnp.einsum("bsd,dhk->bshk", x, lp0["wk"])[:, 0]
        v = jnp.einsum("bsd,dhk->bshk", x, lp0["wv"])[:, 0]
        for i, s in enumerate(seq_ids):
            cache.write_token_kv(0, s, k[i], v[i])
        q = jnp.einsum("bsd,dhk->bshk", x, lp0["wq"])[:, 0]
        out = cache.attend(0, seq_ids, q)
        assert bool(jnp.isfinite(out).all())
        if args.check and not checks:
            checks.append(f"attn_max_abs_err="
                          f"{_check_attention(cache, seq_ids, q, out):.3g}")
            if not ops.interpret_mode():
                assert _kernel_hlo_ok(cache, seq_ids, q)
                checks.append("kernels=tpu_custom_call")

    t0 = time.perf_counter()
    loop.run(decode_fn, max_steps=5000)
    wall = time.perf_counter() - t0
    p = loop.pressures()
    print(f"completed={len(loop.done)}/{args.requests} "
          f"decode_steps={loop.decode_steps} "
          f"compaction_steps={loop.compaction_steps} "
          f"compaction_dmas={cache.compaction_dmas} "
          f"alloc_failures={cache.alloc_failures} "
          f"frag={cache.fragmentation():.3f} "
          f"pressures=(admit={p['admit']:.2f},frag={p['frag']:.2f}) "
          f"wall={wall:.1f}s", flush=True)
    if args.check:
        assert loop.compaction_steps > 0, "no compaction ran"
        print("checks passed: " + " ".join(
            checks + [f"compactions_verified={loop.compaction_steps}"]),
            flush=True)
    return 0 if len(loop.done) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
