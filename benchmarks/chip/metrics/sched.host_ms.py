"""The serve loop's own host work per iteration, in ms: the self time of
the program's span ``serve.sched.step`` (``ServeLoop.step`` less its
children ``serve.sched.decode`` and ``serve.cache.compact``: the
compaction test and pressures, admission, ``append_token``, retiring
finished sequences) over the span's count, every iteration of the loop,
compacting ones included, from ``repro.obs.spans`` as the traced window
recorded them.  The self time also holds the cost of opening and closing
the children's spans.  Moves ``serve_tok_s``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    step = spans.snapshot()["spans"].get("serve.sched.step")
    if step is None or not step["count"]:
        return None
    return step["self_ms"] / step["count"]
