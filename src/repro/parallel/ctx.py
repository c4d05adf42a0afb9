"""Ambient logical-sharding context for activation constraints.

Model code calls ``constrain(x, ("act_batch", None, None))``; inside a
``with activation_rules(mesh, rules):`` scope this lowers to
``with_sharding_constraint`` — pinning activations batch-sharded so the
SPMD partitioner all-gathers FSDP weights per layer instead of
all-reducing activation-sized partial sums.  Outside the scope it is a
no-op (pure single-device execution, kernels, unit tests).

Pallas kernels are custom calls that the SPMD partitioner cannot split:
under a mesh of more than one device the model runs them through
:func:`shard_local`, which hands each device its own shard, laid out by
the operands' logical axes.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .sharding import Rules, spec_for

_state = threading.local()


@contextlib.contextmanager
def activation_rules(mesh: Mesh, rules: Rules):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules)
    try:
        yield
    finally:
        _state.ctx = prev


def constrain(x, axes: Tuple[Optional[str], ...]):
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(x.shape, axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def current() -> Optional[Tuple[Mesh, Rules]]:
    """(mesh, rules) of the enclosing ``activation_rules`` scope, if any."""
    return getattr(_state, "ctx", None)


def platform() -> str:
    """Platform of the devices the traced code runs on: the enclosing
    mesh's, else JAX's default."""
    ctx = current()
    if ctx is None:
        return jax.default_backend()
    return ctx[0].devices.flat[0].platform


def sharded_mesh() -> Optional[Tuple[Mesh, Rules]]:
    """``current()`` where its mesh has more than one device, else None."""
    ctx = current()
    return ctx if ctx is not None and ctx[0].size > 1 else None


def spec_of(x, axes: Tuple[Optional[str], ...]) -> PartitionSpec:
    """The enclosing scope's PartitionSpec of ``x`` by its logical axes."""
    mesh, rules = current()
    return spec_for(x.shape, axes, rules, mesh)


def entry(spec: PartitionSpec, dim: int):
    """The mesh axis (or axes) of ``spec`` on ``dim``, None if replicated."""
    return spec[dim] if dim < len(spec) else None


def shard_local(fn, in_specs, out_specs):
    """``fn`` on each device's shard of the enclosing mesh: operands and
    results laid out by PartitionSpecs; ``fn`` names mesh axes itself for
    its collectives and ``axis_index``."""
    mesh, _ = current()
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
