"""The main path's Pallas kernels, compiled at real widths for a described
TPU v5e that is not attached: the chip's compiler refuses here what it
would refuse on the chip (VMEM over the scoped limit, unaligned tiles).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file. Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.tuner import tuned_matmul_blocks
from repro.hw import target_for_device_kind
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ops import tuned_flash_blocks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def target(topo):
    """The schedule target the device table gives the described chip."""
    return target_for_device_kind(topo.devices[0].device_kind).name


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache off around these compiles
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("s", [512, 600])
def test_flash_compiles_at_yi6b_prefill(s, one_chip, target):
    cfg = get_config("yi-6b")
    bq, bk = tuned_flash_blocks(s, cfg.head_dim, 2, target)

    def spec(heads):
        return jax.ShapeDtypeStruct((1, heads, s, cfg.head_dim), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=bq, block_k=bk)).lower(
        spec(cfg.n_heads), spec(cfg.n_kv_heads), spec(cfg.n_kv_heads)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,n,k", [(1024, 1024, 1024), (2048, 2048, 2048),
                                   (4096, 4096, 4096)])
def test_matmul_compiles_with_tuned_blocks(m, n, k, one_chip, target):
    bm, bn, bk = tuned_matmul_blocks(m, n, k, 2, target)
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    y = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, y: matmul_pallas(
        x, y, bm=bm, bn=bn, bk=bk)).lower(x, y).compile()
    assert "tpu_custom_call" in compiled.as_text()
