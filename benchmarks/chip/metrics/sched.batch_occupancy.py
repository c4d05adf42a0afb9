"""Share of the decode batch's slots that held a sequence, in %: the
counter ``sched.decode_seqs`` (sequences decoded) over ``sched.batch_slots``
(``max_batch`` per decode iteration), from ``repro.obs.spans`` as the
traced window recorded them.  Under 100 where admission waits for pages.
Moves ``serve_tok_s``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    c = spans.snapshot()["counters"]
    slots = c.get("sched.batch_slots", 0)
    if not slots:
        return None
    return 100.0 * c.get("sched.decode_seqs", 0) / slots
