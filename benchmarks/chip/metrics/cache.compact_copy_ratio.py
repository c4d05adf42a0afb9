"""Pages page GC copies per page it had to move: the counter
``cache.compact.pages_copied`` (every page the compaction's copies move,
over every layer's K and V plane) over ``cache.compact.pages_relocated``
(live pages whose index changed, times the planes that hold them), from
``repro.obs.spans`` as the traced window recorded them.  1 is a compaction
that moves only what it must.  Moves ``itl_p95_ms``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    c = spans.snapshot()["counters"]
    relocated = c.get("cache.compact.pages_relocated", 0)
    if not relocated:
        return None
    return c.get("cache.compact.pages_copied", 0) / relocated
