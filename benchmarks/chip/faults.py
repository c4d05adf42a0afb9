"""Faults planted in the timed path, underneath the harness, to show that
the comparison catches them (``run.py --fault <name>``; the tests).

Each is a function that patches the program in this process only.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp


def kv_write_dropped():
    """Serving: the cache keeps its state; no K/V row is written."""
    from repro.serving.kvcache import PagedKVCache
    PagedKVCache.write_token_kv = lambda self, layer, seq_id, k, v: None


def answer_altered():
    """Serving: every attention output the step hands out is off by 5%."""
    from repro.serving.kvcache import PagedKVCache
    attend = PagedKVCache.attend

    def altered(self, layer, seq_ids, q):
        return attend(self, layer, seq_ids, q) * 1.05
    PagedKVCache.attend = altered


def compaction_misplaced():
    """Serving: each compaction copies the first page of the oldest
    sequence over the first page of every other, their tables unchanged:
    prompt rows of one sequence end up under the others, while every row a
    decode step wrote stays in place.  (A swap of two pages would undo
    itself at the next compaction of the same two sequences.)"""
    from repro.serving.kvcache import PagedKVCache
    compact = PagedKVCache.compact

    def misplaced(self):
        n = compact(self)
        firsts = [t[0] for t in self.tables.values() if t]
        if len(firsts) >= 2:
            self.pool = self.pool.at[:, :, jnp.asarray(firsts[1:])].set(
                self.pool[:, :, firsts[0]][:, :, None])
        return n
    PagedKVCache.compact = misplaced


def _wrap_step(wrap):
    from repro.train import step as step_mod
    build = step_mod.build_train_step

    @functools.wraps(build)
    def patched(*a, **kw):
        fn, *rest = build(*a, **kw)
        return (wrap(fn), *rest)
    step_mod.build_train_step = patched


def state_unchanged():
    """Training: the step returns its parameters and optimizer state as
    they came in."""
    def wrap(fn):
        def step(params, opt_state, batch):
            _, _, metrics = fn(params, opt_state, batch)
            return params, opt_state, metrics
        return step
    _wrap_step(wrap)


def half_batch():
    """Training: the step sees the first half of the batch's rows only
    and takes the mean over them."""
    def wrap(fn):
        def step(params, opt_state, batch):
            half = {k: jnp.concatenate([v[:v.shape[0] // 2]] * 2)
                    for k, v in batch.items()}
            return fn(params, opt_state, half)
        return step
    _wrap_step(wrap)


SERVE = ("kv_write_dropped", "answer_altered", "compaction_misplaced")
TRAIN = ("state_unchanged", "half_batch")
