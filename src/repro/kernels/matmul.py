"""Blocked matmul Pallas TPU kernel with Tuna-tunable BlockSpec tiling.

Grid (M/bm, N/bn, K/bk); K is the innermost ("arbitrary") grid dimension so
the f32 VMEM accumulator is revisited across K steps and written back once —
the schedule Tuna's TPU cost model scores (MatmulSpace in core/spaces.py maps
1:1 onto these BlockSpecs; the DMA-per-grid-step and resident-accumulator
semantics mirrored there are exactly what ``pl.pallas_call`` does here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw.tpu_v5e import TPU_V5E


# Pallas trace counter: bumped every time matmul_pallas builds the kernel
# (eager interpret run or inside a jit trace). An AOT-deserialized
# executable from a kernel bundle never re-enters this function, so
# "cold start pays zero Pallas compilations" is assertable as TRACE_COUNT
# staying flat — see kernels.ops.pallas_trace_counts.
TRACE_COUNT = 0


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N] (f32 accumulation, output in x.dtype)."""
    global TRACE_COUNT
    TRACE_COUNT += 1
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{n},{k}) not divisible by blocks ({bm},{bn},{bk})"
    )
    grid = (m // bm, n // bn, k // bk)
    # The cost model budgets the double-buffered x/y/out blocks against the
    # target's fast_mem_bytes. The kernel also holds the f32 accumulator and
    # the dot's f32 result, so at the largest blocks that budget (which is
    # also Mosaic's default scoped-VMEM limit) is exceeded: ask for both.
    vmem = 2 * (bm * bk + bk * bn + bm * bn) * x.dtype.itemsize + 2 * bm * bn * 4
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(TPU_V5E.fast_mem_bytes, vmem + (1 << 20)),
        ),
        interpret=interpret,
    )(x, y)
