"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference can make the
same ones without taking anything from the program.  A leaf is found by
its path of keys in the parameter tree; under ``layers`` the first axis
stacks the layers.  Matrices and embeddings are normal with standard
deviation 1/sqrt(fan-in); the Mamba-2 leaves follow that paper's
initialisation (arXiv:2405.21060): A in [1, 16], dt in [1e-3, 1e-1]
through the softplus, a unit skip, unit norm gains.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ONES = ("norm", "out_norm", "final_norm", "attn_norm", "ffn_norm",
        "d_skip")


def leaf(path: Tuple[str, ...], shape, dtype, key):
    """One leaf, from its own key: the seed's key folded with a hash of
    the leaf's path, so that a reference can make any one leaf again
    without the rest of the tree."""
    key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    name = path[-1]
    stacked = "layers" in path
    dims = shape[1:] if stacked else shape
    if name in ONES:
        return jnp.ones(shape, dtype)
    if name == "a_log":
        a = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(dtype)
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    fan_in = dims[0] if dims else 1
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def make(abstract: Dict[str, Any], key, shardings=None) -> Dict[str, Any]:
    """Arrays shaped and typed like ``abstract`` (a tree of
    ShapeDtypeStruct), from ``key``; ``shardings`` places them."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(key):
        return jax.tree.unflatten(treedef, [
            leaf(tuple(getattr(p, "key", str(p)) for p in path), a.shape,
                 a.dtype, key) for path, a in paths])

    return jax.jit(build, out_shardings=shardings)(key)


def seed_key(seed: int):
    """A JAX PRNG key from any whole number (wider than 32 bits too)."""
    state = np.random.SeedSequence(int(seed) % (1 << 63)).generate_state(
        2, np.uint32)
    return jnp.asarray(state, dtype=jnp.uint32)
