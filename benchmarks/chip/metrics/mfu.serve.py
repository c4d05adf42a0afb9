"""The whole decode step's share of the chip's peak: for every decode
iteration of the window, the least time its required work takes
(``work.serve_decode_step``: the layer-0 projections, the K/V rows
written, the paged attention; the larger of compute and memory time),
summed over the window's length.  Moves ``serve_tok_s``."""

import work


def read(run):
    rec, peaks = run["record"], run["peaks"]
    m = rec["model"]
    least = sum(work.least_time(*work.serve_decode_step(
        st["lengths"], m["d_model"], m["n_heads"], m["kv_heads"],
        m["head_dim"], rec["kv_bytes"], rec["weight_bytes"]), peaks)[0]
        for st in rec["decode_steps"])
    return 100.0 * least / rec["window_s"]
