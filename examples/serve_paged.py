"""Serving example: batched requests through the paged KV-cache with
Scavenger+-style page GC (live-page compaction, pressure-driven
scheduling), using a real reduced model end to end.

Run:  PYTHONPATH=src python examples/serve_paged.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import get_model
from repro.serving import (PagedCacheConfig, PagedKVCache, Request,
                           ServeConfig, ServeLoop)

cfg = get_config("phi3-mini-3.8b", smoke=True)
model = get_model(cfg)
params = model.init(cfg, jax.random.PRNGKey(0))

cache = PagedKVCache(cfg, PagedCacheConfig(n_pages=256, page_size=4))
loop = ServeLoop(cfg, cache, ServeConfig(max_batch=4, frag_threshold=0.2))

rng = np.random.default_rng(0)
for i in range(16):
    loop.submit(Request(rid=i, prompt_len=int(rng.integers(4, 24)),
                        max_new_tokens=int(rng.integers(4, 12))))

# A toy decode_fn: runs the model's first attention layer against the
# paged pool (full multi-layer serving wires every layer the same way).
wk = jax.tree.map(lambda a: a[0], params["layers"])["attn"]


def decode_fn(seq_ids):
    x = jax.random.normal(jax.random.PRNGKey(len(seq_ids)),
                          (len(seq_ids), 1, cfg.d_model), jnp.float32)
    k = jnp.einsum("bsd,dhk->bshk", x, wk["wk"])[:, 0]
    v = jnp.einsum("bsd,dhk->bshk", x, wk["wv"])[:, 0]
    for i, s in enumerate(seq_ids):
        cache.write_token_kv(0, s, k[i], v[i])
    q = jnp.einsum("bsd,dhk->bshk", x, wk["wq"])[:, 0]
    out = cache.attend(0, seq_ids, q)
    assert bool(jnp.isfinite(out).all())


loop.run(decode_fn, max_steps=2000)
print(f"completed={len(loop.done)} decode_steps={loop.decode_steps} "
      f"compactions={loop.compaction_steps} "
      f"fragmentation={cache.fragmentation():.3f}")
assert len(loop.done) == 16
