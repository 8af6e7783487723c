"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which ships with jaxlib, compiles each kernel
for a v5e that is described and not attached, and refuses what the chip
would refuse (tile misalignment, too much VMEM) — faults that interpret mode
on the CPU cannot show. The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library, and every
xdist worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_pack import kv_pack, kv_unpack


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off so nothing warns."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# 16-token pages at qwen2-0.5b's per-layer K width (2 kv heads x 64) and at
# qwen3-moe-235b-a22b's (4 kv heads x 128).
@pytest.mark.parametrize("kv_dim", [128, 512])
def test_kv_pack_and_unpack_compile(one_chip, no_persistent_cache, kv_dim):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((272, 16, kv_dim), jnp.bfloat16)
    buf = sds((136, 16, kv_dim), jnp.bfloat16)
    idx = sds((136,), jnp.int32)
    _assert_kernel(kv_pack.lower(pool, idx, interpret=False).compile())
    _assert_kernel(kv_unpack.lower(pool, buf, idx, interpret=False).compile())


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_attention_compiles(one_chip, no_persistent_cache, head_dim):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((1, 2048, 14, head_dim), jnp.bfloat16)
    kv = sds((1, 2048, 2, head_dim), jnp.bfloat16)
    _assert_kernel(
        flash_attention.lower(q, kv, kv, causal=True, interpret=False).compile())
