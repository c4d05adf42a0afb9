"""Persistent compilation cache shared by the launch entry points."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed directory in the checkout, git-ignored: compiled programs are found
# again by the next run from the same checkout.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep compiled programs across runs; call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Runs on the CPU (tests, rehearsals) are left
    uncached: XLA:CPU warns about host features on every cached load."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.devices()[0].platform != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
