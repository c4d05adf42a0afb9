"""Plain reference of one decode step's attention over a sequence's cache.

Imports nothing of the program.  For each checked sequence it rebuilds
what every decode step should have produced: the step's query and the
K/V row it wrote come from the step's input and the layer-0 weights, made
again from the seed at highest float32 precision; positions the step did
not write (the prompt, which this serving path does not prefill, so its
pages hold what the seed filled the pool with or what an earlier
sequence left there) are the rows the cache holds for them at the close,
read back through the sequence's pages.  Every decode step before a
compaction and every one after it is held to those same rows.
Attention is a plain softmax over the sequence's first ``length`` rows.

``kv_dtype`` rounds every K/V row to a lower precision first: the control.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def project(x, w):
    """x: (B, d) float32, w: (d, H, hd) → (B, H, hd) at highest precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("bd,dhk->bhk", x, w)


def attend_steps(q, k_rows, v_rows, lengths, kv_dtype: Optional[str] = None):
    """q: (T, H, hd) one query per checked step; k_rows/v_rows: (L, Hkv, hd)
    the sequence's rows; lengths: (T,) rows visible at each step.
    Returns (T, H, hd) float32."""
    f32 = jnp.float32
    k, v = k_rows.astype(f32), v_rows.astype(f32)
    if kv_dtype is not None:
        k = k.astype(kv_dtype).astype(f32)
        v = v.astype(kv_dtype).astype(f32)
    t, h, d = q.shape
    hkv = k.shape[1]
    qg = q.astype(f32).reshape(t, hkv, h // hkv, d)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("tkgd,lkd->tkgl", qg, k) / math.sqrt(d)
        pos = jnp.arange(k.shape[0])
        s = jnp.where(pos[None, None, None, :] < lengths[:, None, None, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("tkgl,lkd->tkgd", w, v)
    return out.reshape(t, h, d)
