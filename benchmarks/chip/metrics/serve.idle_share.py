"""Share of the window in which no operation ran on the chip, from the
device trace.  Moves ``serve_tok_s``."""


def read(run):
    tr = run["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
