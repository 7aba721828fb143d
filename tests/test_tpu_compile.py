"""The serving path's Pallas kernels compile for a TPU v5e chip.

Interpret mode, which every other kernel test uses, accepts block shapes
and kernel bodies that the chip's compiler (Mosaic) refuses.  These
tests compile the three kernels for a *described* v5e chip — no chip is
attached — at llama3.2-1b's widths (32 query heads, 8 kv heads,
head_dim 64, f32 pool of 8-token pages) and at the shapes the engine
emits, and check that each compiled program holds the kernel
(``tpu_custom_call``).

The topology is described only inside the module fixture: the TPU
library may be loaded by one process at a time, so describing it while
test modules are imported would make parallel test workers collect
different tests.  The fixture skips where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.tree_attention import tree_attention

H, K, HD = 32, 8, 64           # llama3.2-1b query heads, kv heads, head_dim
PAGES, PAGE = 2048, 8          # the served KV pool
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def one_chip():
    """A single-chip sharding on a described v5e:2x2 host, with JAX's
    persistent compilation cache off: a compile for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, shard, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=shard) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,N,block_b", [
    (32, 8, None),      # the served max_batch, smallest page bucket
    (32, 256, None),    # a wide tree
    (96, 64, None),     # two leaf tiles of 64: the padded tile edge
    (96, 64, 32),       # three tiles of 32
])
def test_tree_attention_compiles_for_v5e(one_chip, B, N, block_b):
    text = _compiled_text(
        lambda q, k, v, pl, pm, pn: tree_attention(
            q, k, v, pl, pm, pn, scale=SCALE, interpret=False,
            block_b=block_b),
        one_chip,
        ((B, H, HD), jnp.float32), ((PAGES, PAGE, K, HD), jnp.float32),
        ((PAGES, PAGE, K, HD), jnp.float32), ((N,), jnp.int32),
        ((N, B), jnp.int8), ((N,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [32, 64])
def test_paged_attention_compiles_for_v5e(one_chip, B):
    T = -(-200 // PAGE)        # block-table width at max_seq_len 200
    text = _compiled_text(
        lambda q, k, v, bt, ln: paged_attention(
            q, k, v, bt, ln, scale=SCALE, interpret=False),
        one_chip,
        ((B, H, HD), jnp.float32), ((PAGES, PAGE, K, HD), jnp.float32),
        ((PAGES, PAGE, K, HD), jnp.float32), ((B, T), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,T", [(1, 8), (4, 16), (32, 256)])
def test_flash_prefill_compiles_for_v5e(one_chip, B, T):
    """Prefill buckets: power-of-two rows and tokens (>= 8 tokens)."""
    text = _compiled_text(
        lambda q, k, v: flash_prefill(q, k, v, scale=SCALE,
                                      interpret=False),
        one_chip,
        ((B, T, H, HD), jnp.float32), ((B, T, K, HD), jnp.float32),
        ((B, T, K, HD), jnp.float32))
    assert "tpu_custom_call" in text
