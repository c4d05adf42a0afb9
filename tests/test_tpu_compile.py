"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e (2x2), at real widths.

Nothing runs: the TPU compiler installed with JAX compiles for chips that
are described, not attached, and refuses what the chip's compiler would
refuse (block shapes off the (8, 128) tiling, too much VMEM), which
interpret mode never checks.  The topology is described inside a fixture,
never at import, so every pytest worker collects the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.gc_compact import gather_page_blocks, move_pages
from repro.kernels.ops import gmm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.train_attention import causal_attention
from repro.serving.kvcache import kv_scatter_row


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache off meanwhile
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in hlo      # the Mosaic kernel, not a fallback


@pytest.mark.parametrize("heads,kv_heads", [(16, 16), (32, 8)])
def test_paged_attention_compiles_for_v5e(one_chip, heads, kv_heads):
    # olmo-1b widths (and a GQA split): 2048 pages of 16 tokens, head 128,
    # 8 sequences of up to 64 pages
    bf16 = jnp.bfloat16
    _compile(paged_attention, one_chip,
             ((8, heads, 128), bf16), ((2048, 16, kv_heads, 128), bf16),
             ((2048, 16, kv_heads, 128), bf16), ((8, 64), jnp.int32),
             ((8,), jnp.int32))


@pytest.mark.parametrize("block_pages", [1, 4])
def test_gather_page_blocks_compiles_for_v5e(one_chip, block_pages):
    # one (layer, K) plane of the olmo-1b pool at page 16: 16 heads x 128
    _compile(gather_page_blocks, one_chip,
             ((2048, 16, 2048), jnp.bfloat16), ((64,), jnp.int32),
             block_pages=block_pages)


@pytest.mark.parametrize("moves", [16, 256])
def test_move_pages_compiles_for_v5e_in_place(one_chip, moves):
    # the whole olmo-1b pool (16 layers x K/V, 1024 pages of 16 tokens,
    # 16 heads x 128): the output is the donated pool, with no temporary
    pool = ((16, 2, 1024, 16, 16, 128), jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (pool, ((moves,), jnp.int32), ((moves,), jnp.int32))]
    compiled = move_pages.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 ** 31
    assert mem.temp_size_in_bytes == 0


def test_flash_attention_compiles_for_v5e(one_chip):
    bf16 = jnp.bfloat16
    _compile(flash_attention, one_chip, ((1, 2048, 16, 128), bf16),
             ((1, 2048, 16, 128), bf16), ((1, 2048, 16, 128), bf16))


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)])
def test_grouped_matmul_compiles_for_v5e(one_chip, k, n):
    # the nemotron3-nano cell's expert projections, forward and backward:
    # 4 x 8192 tokens x top-6 sorted pairs, 8 held experts of 2688 x 1856
    # (up) and 1856 x 2688 (down), a ninth group for the other pairs
    def loss(x, w, sizes):
        return jnp.sum(gmm(x, w, sizes, interpret=False)
                       .astype(jnp.float32))
    _compile(jax.jit(jax.grad(loss, argnums=(0, 1))), one_chip,
             ((4 * 8192 * 6, k), jnp.bfloat16), ((8, k, n), jnp.bfloat16),
             ((9,), jnp.int32))


def test_training_attention_compiles_for_v5e(one_chip):
    # the cell's attention layer at S 8192, 32 query and 2 KV heads of
    # 128, forward and backward (the splash kernels' fused backward)
    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))
    bf16 = jnp.bfloat16
    _compile(jax.jit(jax.grad(loss, argnums=(0, 1, 2))), one_chip,
             ((1, 8192, 32, 128), bf16), ((1, 8192, 2, 128), bf16),
             ((1, 8192, 2, 128), bf16))


@pytest.mark.parametrize("kernel", ["attention", "experts"])
def test_kernels_run_per_chip_on_a_v5e_mesh(one_chip, monkeypatch, kernel):
    # on a (4, 1) data mesh each chip runs the Pallas kernel on its own
    # rows, forward and backward, with no operand gathered from the others:
    # olmo1b-train-dp4's attention (64 rows of 2048, 16 heads of 128) and a
    # granite-sized expert layer (40 experts of 1536 x 512, top-8)
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.mesh import _mesh
    from repro.models import modules, moe
    from repro.parallel.ctx import activation_rules
    from repro.parallel.sharding import default_rules
    monkeypatch.setattr(ops, "interpret_mode", lambda interpret=None: False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = _mesh((4, 1), ("data", "model"), topo.devices)
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    bf16 = jnp.bfloat16
    if kernel == "attention":
        fn, diff = modules._kernel_attention, (0, 1, 2)
        args = [((64, 2048, 16, 128), bf16, rows)] * 3
    else:
        cfg = get_config("granite_moe_3b_a800m")
        names = sorted(moe.specs(cfg))
        fn, diff = (lambda x, *ws: moe.moe(dict(zip(names, ws)), x, cfg)[0],
                    tuple(range(len(names) + 1)))
        args = [((8, 2048, cfg.d_model), bf16, rows)] + [
            (moe.specs(cfg)[k].shape, jnp.float32, whole) for k in names]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in args]

    def loss(*a):
        with activation_rules(mesh, default_rules(mesh)):
            return jnp.sum(fn(*a).astype(jnp.float32))
    hlo = jax.jit(jax.grad(loss, argnums=diff)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo


def test_kv_scatter_row_compiles_for_v5e_in_place(one_chip):
    # one token's K and V row into the whole olmo-1b pool: the output is
    # the donated pool, and nothing the size of a plane is made
    pool = ((16, 2, 1024, 16, 16, 128), jnp.bfloat16)
    row = ((16, 128), jnp.float32)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (pool, ((3,), jnp.int32), row, row)]
    mem = kv_scatter_row.lower(*args).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 2 ** 31
    assert mem.temp_size_in_bytes < 2 ** 20
