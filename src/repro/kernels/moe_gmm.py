"""Grouped matmul of a dropless expert layer (Pallas TPU kernel ``moe_gmm``).

The rows of ``x`` [R, K] are grouped by expert, each group padded to a
multiple of the row tile ``tm``, so that every row tile belongs to one
expert: ``tile_expert[t]`` names it, and tiles from ``active`` on are
padding. Tile t computes ``x[t*tm:(t+1)*tm] @ w[tile_expert[t]]``.

``w`` may be a stack of layers [G, E, K, N] with the layer's index: the
kernel then reads the layer's experts where they lie (a slice of the stack
taken before a custom call would be copied first).

The grid runs over (column tiles of the output, row tiles, tiles of K).
The expert weights' block index changes only where the expert does, so each
touched expert's weights are read once per column tile, that is once in
all; an expert with no rows has no tile and is not read. Padding tiles
repeat the last active tile's blocks (no new read) and write zeros.

Off the TPU the same layout runs as a batched product of each row tile
with its expert's weights, gathered; that is also the kernel's gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_W_BLOCK_BYTES = 6 * 2 ** 20  # one weight block; two are in flight
_VMEM_LIMIT = 64 * 2 ** 20


def _tile(dim: int, most: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``most``; ``dim`` itself where none does."""
    best = 0
    for t in range(128, min(dim, most) + 1, 128):
        if dim % t == 0:
            best = t
    return best or dim


def _kernel(tile_expert, active, layer, x_ref, w_ref, o_ref, acc_ref, *,
            nk: int):
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < active[0])
    def _accumulate():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gmm_pallas(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
               active: jax.Array, tm: int, layer, interpret: bool = False):
    """x [R, K] (R a multiple of ``tm``), w [G, E, K, N] at ``layer`` ->
    [R, N] in x's dtype; ``tile_expert`` [R // tm] int32, ``active`` [1]
    int32."""
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    r, kdim = x.shape
    n = w.shape[3]
    tn = _tile(n, 2048)
    tk = _tile(kdim, max(128, _W_BLOCK_BYTES // (tn * w.dtype.itemsize)))

    def x_map(j, t, k, te, na, g):  # a padding tile repeats the last active one
        return jnp.minimum(t, jnp.maximum(na[0] - 1, 0)), k

    def w_map(j, t, k, te, na, g):
        return g[0], te[jnp.minimum(t, jnp.maximum(na[0] - 1, 0))], k, j

    return pl.pallas_call(
        functools.partial(_kernel, nk=kdim // tk),
        out_shape=jax.ShapeDtypeStruct((r, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, r // tm, kdim // tk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((None, None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, k, te, na, g: (t, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm",
    )(tile_expert, active, layer, x, w)


def gmm_reference(x, w, tile_expert, tm: int, layer=None):
    """The same product in ``jax.numpy``: each row tile of x times its
    expert's weights (padding rows are zero, so padding tiles give zeros)."""
    w = w if layer is None else w[layer]
    r, k = x.shape
    out = jnp.einsum("tmk,tkn->tmn", x.reshape(r // tm, tm, k), w[tile_expert],
                     preferred_element_type=jnp.float32)
    return out.reshape(r, -1).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm_tpu(x, w, tile_expert, active, layer, tm):
    return gmm_pallas(x, w, tile_expert, active, tm, layer)


def _gmm_fwd(x, w, tile_expert, active, layer, tm):
    out = _gmm_tpu(x, w, tile_expert, active, layer, tm)
    return out, (x, w, tile_expert, layer)


def _gmm_bwd(tm, res, g):
    x, w, tile_expert, layer = res
    _, vjp = jax.vjp(lambda x, w: gmm_reference(x, w, tile_expert, tm, layer),
                     x, w)
    dx, dw = vjp(g)
    return dx, dw, None, None, None


_gmm_tpu.defvjp(_gmm_fwd, _gmm_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gmm(x, w, tile_expert, active, tm: int, layer=None):
    """The grouped product of the module docstring: the Pallas kernel on a
    TPU, ``gmm_reference`` elsewhere."""
    if not _on_tpu():
        return gmm_reference(x, w, tile_expert, tm, layer)
    if layer is None:
        w, layer = w[None], 0
    return _gmm_tpu(x, w, tile_expert, active, jnp.asarray(layer, jnp.int32), tm)
