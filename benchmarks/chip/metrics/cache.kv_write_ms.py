"""Device time per decode iteration of the programs that
``PagedKVCache.write_token_kv`` dispatches: the eager scatter of one K
and one V row into the pool, each a whole-pool update, named in the trace
by JAX's scatter program.  Moves ``serve_tok_s``."""

NAMES = ("scatter",)


def read(run):
    steps = len(run["record"]["decode_steps"])
    spent = sum(t for name, t in run["trace"]["programs"].items()
                if any(n in name for n in NAMES))
    if not steps or not spent:
        return None
    return 1e3 * spent / steps
