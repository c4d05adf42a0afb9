"""The cache manager's host time per decode iteration, in ms: the total
time of the program's spans ``serve.cache.kv_write`` (each
``PagedKVCache.write_token_kv``: the cast and the two K/V scatters'
dispatch) and ``serve.cache.attend`` (``attend``: the page table's build
and upload, ``serve.cache.page_table``, and the kernel's dispatch) over
the counter ``sched.decode_iterations``, from ``repro.obs.spans`` as the
traced window recorded them.  Most of it is device back-pressure, not
host work: each eager ``.at[].set`` dispatch waits until the device has
freed a pool for its output.  Moves ``serve_tok_s``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    snap = spans.snapshot()
    n = snap["counters"].get("sched.decode_iterations", 0)
    names = ("serve.cache.kv_write", "serve.cache.attend")
    if not n or not any(s in snap["spans"] for s in names):
        return None
    return sum(snap["spans"][s]["total_ms"] for s in names
               if s in snap["spans"]) / n
