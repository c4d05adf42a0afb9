"""The expert layer's grouped matmul's share of its roofline: the least
time the window's expert MLPs need on this chip (``work_models.gmm_train``
over the pairs routed to the held experts, every expert layer of every
step, the larger of its compute and memory limits) over the device time
of the ``gmm`` and ``tgmm`` kernels in the trace.  Moves ``train_tok_s``."""

import work
import work_models


def read(run):
    rec = run["record"]
    spent = rec.get("device_s", {}).get("gmm")
    if not spent or not rec.get("window_pairs"):
        return None
    sh = rec["shape"]
    least, _ = work.least_time(*work_models.gmm_train(
        rec["window_pairs"], sh["experts_held"], sh["d_model"], sh["d_ff"],
        calls=rec["window_moe_calls"]), run["peaks"])
    return 100.0 * least / spent
