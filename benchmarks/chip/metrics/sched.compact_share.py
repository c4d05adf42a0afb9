"""Share of the window spent in compacting iterations of the serve loop
(``ServeLoop.step`` choosing ``PagedKVCache.compact``), on the host clock:
every iteration ends with the pool synced.  Moves ``itl_p95_ms``."""


def read(run):
    rec = run["record"]
    spent = sum(it["end"] - it["start"] for it in rec["iterations"]
                if it["kind"] == 1)
    return 100.0 * spent / rec["window_s"]
