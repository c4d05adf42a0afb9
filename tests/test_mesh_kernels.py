"""The Pallas kernels of the model path under a mesh of several devices.

The grouped matmul of the expert layer (``models.moe``) and the training
attention (``kernels.train_attention``) are custom calls that the SPMD
partitioner cannot split, so the model runs them on each device's shard
(``parallel.ctx.shard_local``).  Each case runs in a fresh process on
eight CPU devices (a (2, 4) data x model mesh, the default rules) and
compares the output and every gradient with the same computation on one
device.  Float32 throughout; only the order of the sums differs, so the
tolerances are a few float32 roundings of a layer.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def _moe_case(case: str):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import moe
    from repro.models.modules import axes_tree, materialize

    f32 = jnp.float32
    if case == "experts_sharded":     # 8 experts over 4: 2 a device
        cfg = dataclasses.replace(get_config("granite_moe_3b_a800m", smoke=True),
                                  n_experts=8, top_k=2)
    elif case == "width_sharded":     # 6 experts do not split over 4
        cfg = dataclasses.replace(get_config("granite_moe_3b_a800m", smoke=True),
                                  n_experts=6, top_k=2, d_ff=64)
    else:                             # a chip's share: 4 held of 16
        cfg = get_config("nemotron3_nano_30b_a3b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype=f32)
    tree = materialize(moe.specs(cfg), jax.random.PRNGKey(0), False, f32)
    axes = axes_tree(moe.specs(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), f32)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape, f32)

    def loss(p, x):
        y, counts = moe.moe(p, x, cfg)
        return jnp.sum(y * ct), counts

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    return grad, (tree, x), (axes, ("act_batch", None, None))


def _attention_case(case: str):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import modules

    f32 = jnp.float32
    # 4 query heads over 4 KV heads split over the model axis with them;
    # 4 over 2, which do not split over 4: batch rows only
    kv = 4 if case == "attention_heads" else 2
    cfg = dataclasses.replace(get_config("olmo-1b", smoke=True), n_heads=4,
                              kv_heads=kv, head_dim_override=128,
                              compute_dtype=f32)
    specs = modules.attention_specs(cfg)
    tree = modules.materialize(specs, jax.random.PRNGKey(0), False, f32)
    axes = modules.axes_tree(specs)
    s = 256
    x = jax.random.normal(jax.random.PRNGKey(1), (4, s, cfg.d_model), f32)
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape, f32)
    pos = jnp.tile(jnp.arange(s), (4, 1))

    def loss(p, x):
        y, _ = modules.gqa_attention(p, x, pos, cfg)
        return jnp.sum(y * ct), jnp.zeros(())

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    return grad, (tree, x), (axes, ("act_batch", None, None))


def compare(case: str) -> str:
    """Run ``case`` on the (2, 4) mesh and on one device; raise on a
    mismatch, else say which paths ran."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_host_mesh
    from repro.models import modules
    from repro.parallel import ctx
    from repro.parallel.sharding import default_rules, spec_for

    assert jax.device_count() == 8
    kernel = case.startswith("attention")
    if kernel:
        # the splash path is the TPU's: take it here, in interpret mode
        from repro.kernels.train_attention import block_for
        modules._kernel_fits = lambda s: block_for(s) > 0
    build = _attention_case if kernel else _moe_case
    fn, args, axes = build(case)
    mesh = make_host_mesh(model=4)
    rules = default_rules(mesh)

    def shard(a, ax):
        return jax.device_put(a, NamedSharding(
            mesh, spec_for(a.shape, ax, rules, mesh)))

    tree = jax.tree.map(shard, args[0], axes[0],
                        is_leaf=lambda a: isinstance(a, jax.Array))
    x = shard(args[1], axes[1])

    calls = []
    real = ctx.shard_local

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)
    ctx.shard_local = counted

    def on_mesh(p, x):
        with ctx.activation_rules(mesh, rules):
            return fn(p, x)
    with jax.default_matmul_precision("highest"):
        (l1, aux1), g1 = jax.jit(on_mesh)(tree, x)
        (l0, aux0), g0 = jax.jit(fn)(*args)
    assert calls, "the kernel did not run on the mesh's shards"
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    jax.tree.map(np.testing.assert_array_equal, aux1, aux0)
    flat0 = jax.tree_util.tree_flatten_with_path(g0)[0]
    flat1 = jax.tree.leaves(g1)
    for (path, w), g in zip(flat0, flat1):
        scale = float(np.max(np.abs(w))) or 1.0
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    return f"ok {case}"


@pytest.mark.parametrize("case", ["experts_sharded", "width_sharded",
                                  "held_share", "attention_heads",
                                  "attention_rows"])
def test_kernel_on_mesh_matches_one_device(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent / "src"), str(HERE)]))
    code = f"import test_mesh_kernels as t; print(t.compare({case!r}))"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=HERE.parent)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert r.stdout.strip().splitlines()[-1] == f"ok {case}"
