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
from repro.kernels.paged_attention import paged_attention


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
