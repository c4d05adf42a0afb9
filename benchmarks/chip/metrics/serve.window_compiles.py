"""Programs compiled or loaded from the persistent cache inside the
window, from JAX's own compile events: set-up should have warmed every
shape, so this reads 0.  Moves ``itl_p95_ms``."""


def read(run):
    return run["window_compiles"]
