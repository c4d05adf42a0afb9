"""The paged-attention kernel's share of its roofline: the least time the
window's decode attention needs on this chip (``work.paged_attention``
over every decode step's lengths, the larger of its compute and memory
limits) over the kernel's device time in the trace.  Moves
``serve_tok_s``."""

import work


def read(run):
    rec, peaks = run["record"], run["peaks"]
    m = rec["model"]
    spent = sum(t for name, t in run["trace"]["programs"].items()
                if "paged_attention" in name)
    if not spent:
        return None
    least = sum(work.least_time(*work.paged_attention(
        st["lengths"], m["n_heads"], m["kv_heads"], m["head_dim"],
        rec["kv_bytes"]), peaks)[0] for st in rec["decode_steps"])
    return 100.0 * least / spent
