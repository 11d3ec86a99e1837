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

from bench.trace import flash_shapes
from repro.configs.base import get_config
from repro.core.tuner import tuned_matmul_blocks
from repro.hw import target_for_device_kind
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ops import tuned_flash_blocks
from repro.launch.engine import init_state, programs
from repro.models.model import Model


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


def _op_text(compiled) -> str:
    """The compiled module printed as a profiler trace names its
    operations: operand shapes, no metadata or backend config."""
    from jax._src.lib import _jax

    opts = _jax.HloPrintOptions()
    opts.print_metadata = False
    opts.print_backend_config = False
    opts.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(opts)


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
    # the kernel's operation text as a device trace shows it: named after
    # the kernel, and parsed by the benchmark's flash_roofline
    ops = [ln.strip() for ln in _op_text(compiled).splitlines()
           if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(ops) == 1 and ops[0].startswith("%flash_attention"), ops
    assert flash_shapes(ops[0]) == (cfg.n_heads, cfg.n_kv_heads, s,
                                    cfg.head_dim)


@pytest.mark.parametrize("m,n,k", [(1024, 1024, 1024), (2048, 2048, 2048),
                                   (4096, 4096, 4096)])
def test_matmul_compiles_with_tuned_blocks(m, n, k, one_chip, target):
    bm, bn, bk = tuned_matmul_blocks(m, n, k, 2, target)
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    y = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, y: matmul_pallas(
        x, y, bm=bm, bn=bn, bk=bk)).lower(x, y).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "%matmul" in _op_text(compiled)


@pytest.mark.parametrize("program", ["decode", "insert"])
@pytest.mark.parametrize("slots,cap", [(8, 2048), (4, 3648)])
def test_engine_updates_the_cache_in_place(program, slots, cap, one_chip):
    """The engine's decode step and insert at yi-6b width, at the shapes of
    the benchmark's two cells: the live cache is donated and written in
    place, so the whole cache is aliased to the output and no temporary is
    as large as one layer's keys (a copy of a layer's slice, or of the
    cache, would be)."""
    cfg = get_config("yi-6b")
    model = Model(cfg)

    def abstract(make, *args):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make, *args))

    cache = abstract(lambda: model.init_cache(slots, cap))
    _, decode, insert = programs(model, cap)
    if program == "decode":
        tok = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
        lowered = decode.lower(abstract(model.init, jax.random.key(0)), cache,
                               tok, tok)
    else:
        one = abstract(lambda: model.init_cache(1, cap))
        lowered = insert.lower(cache, one, jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=one_chip))
    mem = lowered.compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    layer_k = slots * cfg.n_kv_heads * cap * cfg.head_dim * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_k


def test_moonlight_decode_step_in_place(one_chip, monkeypatch):
    """The engine's decode step of the benchmark's moonlight-16b-a3b-9l cell
    at published widths, 64 slots and cap 2048, with the grouped-matmul and
    the latent-attention kernels: the donated state (latent cache and MoE
    counters) is aliased whole, no temporary reaches a tenth of one layer's
    latent cache (a copy of it, of the scan's slice of it for the latent
    kernel, or of one layer's expert weights, would), the kernels'
    operations carry the names ``moe_gmm`` and ``latent_decode``, one
    ``latent_decode`` in each latent segment's scan body, and no fusion
    reads the latent cache (the attention einsums over all ``cap`` rows)."""
    import dataclasses
    import re

    from repro.kernels import latent_decode, moe_gmm

    monkeypatch.setattr(moe_gmm, "_on_tpu", lambda: True)
    monkeypatch.setattr(latent_decode, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=9)
    model = Model(cfg)
    slots, cap = 64, 2048

    def abstract(make, *args):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            jax.eval_shape(make, *args))

    state = abstract(lambda: init_state(model, slots, cap))
    _, decode, _ = programs(model, cap)
    tok = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = decode.lower(abstract(model.init, jax.random.key(0)), state,
                            tok, tok).compile()
    mem = compiled.memory_analysis()
    cache, counts = state
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    layer_latent = slots * cap * cfg.mla.cache_dim * 2
    assert cache_bytes == 9 * layer_latent
    assert cache_bytes <= mem.alias_size_in_bytes <= cache_bytes + 512
    assert mem.temp_size_in_bytes < layer_latent // 10
    text = _op_text(compiled)
    ops = [ln.strip() for ln in text.splitlines()
           if 'custom_call_target="tpu_custom_call"' in ln]
    gmm = [o for o in ops if o.startswith("%moe_gmm")]
    latent = [o for o in ops if o.startswith("%latent_decode")]
    assert len(gmm) == 3 and len(latent) == len(cache) == 2, ops
    assert len(ops) == len(gmm) + len(latent), ops
    reads = re.compile(rf"fusion\(.*{slots},{cap},{cfg.mla.cache_dim}\]")
    assert not [ln for ln in text.splitlines() if reads.search(ln)]
