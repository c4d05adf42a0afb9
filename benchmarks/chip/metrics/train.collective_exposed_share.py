"""Share of the window in which only collectives (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute) run on a chip,
no other operation beside them: the collective time that compute does not
hide, from the device trace's op lines, mean over the chips.  Moves
``train_tok_s``."""


def read(run):
    only = run["record"].get("device_s", {}).get("collective_only")
    if only is None:
        return None
    return 100.0 * only / run["trace"]["window_s"]
