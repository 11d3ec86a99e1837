"""Flash-attention (forward) Pallas TPU kernel, GQA-aware, causal-capable.

Online-softmax blocked attention (Dao et al.) re-tiled for TPU: VMEM-resident
running (m, l, acc) scratch revisited across KV grid steps; KV is the
innermost "arbitrary" grid dimension; with ``causal=True`` fully-masked KV
blocks are skipped via ``pl.when`` (no MXU work issued for blocks strictly
above the diagonal).

Block sizes (block_q, block_k) are Tuna-tunable; ``ops.attention`` asks the
static tuner for them per shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30

# Pallas trace counter (see kernels/matmul.py TRACE_COUNT): flat when the
# call was served by an AOT kernel-bundle executable instead of tracing.
TRACE_COUNT = 0


def _flash_kernel(
    q_ref,  # [1, block_q, d]
    k_ref,  # [1, block_k, d]
    v_ref,  # [1, block_k, d]
    o_ref,  # [1, block_q, d]
    m_ref,  # [block_q, 128] scratch (lane-replicated running max)
    l_ref,  # [block_q, 128] scratch
    acc_ref,  # [block_q, d] scratch
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = corr * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip KV blocks strictly above the causal diagonal
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, S, D]
    v: jax.Array,  # [B, Hkv, S, D]
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    global TRACE_COUNT
    TRACE_COUNT += 1
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)

    # fold (batch, q-head) into one "parallel" grid axis h:
    #   batch = h // hq, q-head = h % hq, kv row = batch*hkv + q-head//group
    qf = q.reshape(b * hq, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    grid = (b * hq, s // block_q, s // block_k)

    def kv_map(h, i, kk):
        return ((h // hq) * hkv + (h % hq) // group, kk, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            nk=grid[2],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, kk: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, kk: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, s, d)
