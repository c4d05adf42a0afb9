"""Host time of one page GC compaction, in ms: the total time of the
program's span ``serve.cache.compact`` (``PagedKVCache.compact``: the plan
of the stable pack in NumPy, the dispatch of ``gc_compact.move_pages``
over the pages that move, unsynced, and the rewrite of the page tables and
the free list) over its count, from ``repro.obs.spans`` as the traced
window recorded them.  Moves ``itl_p95_ms``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    compact = spans.snapshot()["spans"].get("serve.cache.compact")
    if compact is None or not compact["count"]:
        return None
    return compact["total_ms"] / compact["count"]
