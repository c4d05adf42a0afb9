"""The training attention kernel's share of its roofline: the least time
the window's causal attention needs on this chip
(``work_models.attention_train``, forward and backward, every attention
layer of every step) over the device time of the ``splash_*`` kernels in
the trace.  Moves ``train_tok_s``."""

import work
import work_models


def read(run):
    rec = run["record"]
    spent = rec.get("device_s", {}).get("attn")
    sh = rec["shape"]
    if not spent or not sh["attn_layers"]:
        return None
    least, _ = work.least_time(*work_models.attention_train(
        sh["batch"], sh["seq"], sh["n_heads"], sh["kv_heads"],
        sh["head_dim"]), run["peaks"])
    return 100.0 * least * rec["steps"] * sh["attn_layers"] / spent
