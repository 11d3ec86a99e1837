"""Decode attention over a latent cache, read once and only up to each
row's position (Pallas TPU kernel ``latent_decode``).

One query per row, ``qc`` [B, H, C], against the layer's latent cache in
the stack [G, B, CAP, C] (C is the latent and the rope key, side by side):
for each row the kernel reads the blocks of ``blk`` cache rows that hold
its positions below ``pos[b]``, ``ceil(pos[b] / blk)`` of them, and nothing
for a row at position 0 (a free slot). For each block it takes the scores
``qc . block^T`` (bf16 operands, f32 accumulation), keeps a running max and
sum in f32, and adds ``p . block[:, :value_dim]`` (p in the cache's dtype,
f32 accumulation): the block is both key and value, so the rope columns
are dropped in VMEM and the cache is read once. It returns the
unnormalised accumulator [B, H, value_dim], the max and the sum [B, H];
the caller merges in the token's own row and normalises.

The cache is read where it lies in the stack, at the layer's index (a
slice of the stack taken before a custom call would be copied first). On
the TPU the stack [G, B, CAP, 576] is laid out with the positions minor
(576 is not a multiple of 128, CAP is), so the kernel takes it as [G, B,
C, CAP], which is the same bytes, and copies blocks [C, blk]. The (row,
block) pairs to read form a flat list, row by row, built from ``pos``
outside the kernel; the kernel is one grid step whose loop copies pair
k + 1 while it computes pair k (two buffers), so its cost follows the
blocks read, not B x CAP / blk. The query and the outputs stay in VMEM for
the whole call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_BYTES = 320 * 2 ** 10  # one block of cache rows; two are in flight
_VMEM_LIMIT = 24 * 2 ** 20
_LANES = 128  # the running max and sum are lane-replicated
NEG_INF = -1e30


def block_rows(cap: int, width: int, itemsize: int) -> int:
    """Cache rows the kernel reads at a time: the largest power of two that
    divides ``cap`` and whose rows fit ``_BLOCK_BYTES``."""
    most = max(1, _BLOCK_BYTES // (width * itemsize))
    return min(cap & -cap, 1 << (most.bit_length() - 1))


def _kernel(pos_ref, row_ref, blk_ref, n_ref, layer_ref, q_ref, c_hbm,
            acc_ref, m_ref, l_ref, buf, sem, *, blk: int, value_dim: int):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    n = n_ref[0]

    def copy(k, slot):  # item k's block: [C, blk] of its row's cache
        return pltpu.make_async_copy(
            c_hbm.at[layer_ref[0], row_ref[k], :, pl.ds(blk_ref[k] * blk, blk)],
            buf.at[slot], sem.at[slot])

    @pl.when(n > 0)
    def _first():
        copy(0, 0).start()

    def body(k, carry):
        slot = k % 2

        @pl.when(k + 1 < n)
        def _next():
            copy(k + 1, 1 - slot).start()

        copy(k, slot).wait()
        b = row_ref[k]
        kv = buf[slot]  # [C, blk]
        s = jax.lax.dot_general(q_ref[b], kv, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [H, blk]
        idx = blk_ref[k] * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < pos_ref[b], s, NEG_INF)
        m_prev = m_ref[b][:, :1]  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_ref[b][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[b] = acc_ref[b] * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:value_dim], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[b] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[b] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def latent_decode(qc: jax.Array, cache: jax.Array, pos: jax.Array, layer,
                  value_dim: int, interpret: bool = False):
    """qc [B, H, C], cache [G, B, CAP, C] at ``layer``, pos [B] int32 ->
    (acc [B, H, value_dim] f32, max [B, H] f32, sum [B, H] f32) over each
    row's cache rows below its position, ``block_rows`` at a time."""
    _, b, cap, c = cache.shape
    h = qc.shape[1]
    blk = block_rows(cap, c, cache.dtype.itemsize)
    # the flat list of (row, block) items, row by row; n of them are read
    nb = (jnp.minimum(pos, cap) + blk - 1) // blk
    ends = jnp.cumsum(nb)
    k = jnp.arange(b * (cap // blk), dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= k[:, None], axis=1), b - 1)
    row = row.astype(jnp.int32)
    blk_of = (k - (ends - nb)[row]).astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, blk=blk, value_dim=value_dim),
        out_shape=(jax.ShapeDtypeStruct((b, h, value_dim), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[whole(b, h, c), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(whole(b, h, value_dim), whole(b, h, _LANES),
                       whole(b, h, _LANES)),
            scratch_shapes=[pltpu.VMEM((2, c, blk), cache.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="latent_decode",
    )(pos.astype(jnp.int32), row, blk_of, ends[-1:].astype(jnp.int32), layer,
      qc, jnp.swapaxes(cache, 2, 3))
    return acc, m[..., 0], l[..., 0]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"
