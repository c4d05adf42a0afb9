"""Host time of one page GC compaction, in ms: the total time of the
program's span ``serve.cache.compact`` (``PagedKVCache.compact``: the plan,
the layer-0 kernel call synced by its page index, the whole-pool permute's
dispatch, the table rewrite) over its count, from ``repro.obs.spans`` as
the traced window recorded them.  Moves ``itl_p95_ms``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    compact = spans.snapshot()["spans"].get("serve.cache.compact")
    if compact is None or not compact["count"]:
        return None
    return compact["total_ms"] / compact["count"]
