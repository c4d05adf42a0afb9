"""How unevenly the router loads the held experts: the mean over expert
layers and steps of the most pairs any held expert got over the mean pairs
per held expert, from the counters that the training loop records after
each loss readback (``repro.train.step.record_routing``:
``moe.pairs_max``, ``moe.pairs``, ``moe.layer_steps``,
``moe.held_slots``).  1 is an even load; the most loaded expert sets the
grouped matmul's longest group.  Moves ``train_tok_s``."""


def read(run):
    try:
        from repro.obs import spans
    except ImportError:           # a program without the recorder
        return None
    c = spans.snapshot()["counters"]
    if not c.get("moe.pairs") or not c.get("moe.layer_steps"):
        return None
    mean = c["moe.pairs"] / c["moe.held_slots"]
    return c["moe.pairs_max"] / c["moe.layer_steps"] / mean
