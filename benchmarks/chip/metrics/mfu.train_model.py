"""Model FLOP utilisation of a ``train_model`` cell: the forward and
backward operations the model requires per token (``work_models``;
recomputation not counted) times the tokens trained per second in the
window, over the chips' bf16 peak.  Moves ``train_tok_s``."""


def read(run):
    rec = run["record"]
    rate = rec["tokens"] / rec["window_s"]
    return (100.0 * rec["flops_per_token"] * rate
            / (run["chips"] * run["peaks"]["bf16_flops_per_s"]))
