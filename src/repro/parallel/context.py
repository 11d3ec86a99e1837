"""Activation-sharding context.

Model code is mesh-agnostic; the launcher (dryrun/train/serve) installs the
data-parallel axes here and layers call ``constrain_tokens`` /
``constrain_seq`` at block boundaries. With no context installed (unit tests,
single-device runs) these are no-ops.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_DP_AXES: Optional[Tuple[str, ...]] = None
_TP_AXIS: Optional[str] = None
_TP_SIZE: int = 1
_SP_SEQ: bool = False  # sequence-parallel activations between blocks
_MESH = None  # concrete mesh for shard_map code paths


def install(dp_axes: Tuple[str, ...], tp_axis: str = "model",
            tp_size: int = 1, sp_seq: bool = False, mesh=None) -> None:
    global _DP_AXES, _TP_AXIS, _TP_SIZE, _SP_SEQ, _MESH
    _DP_AXES, _TP_AXIS, _TP_SIZE, _SP_SEQ, _MESH = (
        tuple(dp_axes), tp_axis, tp_size, sp_seq, mesh
    )


def clear() -> None:
    global _DP_AXES, _TP_AXIS, _TP_SIZE, _SP_SEQ, _MESH
    _DP_AXES, _TP_AXIS, _TP_SIZE, _SP_SEQ, _MESH = None, None, 1, False, None


def mesh():
    return _MESH


def dp_axes():
    return _DP_AXES


@contextlib.contextmanager
def activation_sharding(dp_axes: Tuple[str, ...], tp_axis: str = "model",
                        tp_size: int = 1, sp_seq: bool = False):
    prev = (_DP_AXES, _TP_AXIS, _TP_SIZE, _SP_SEQ)
    install(dp_axes, tp_axis, tp_size, sp_seq)
    try:
        yield
    finally:
        install(*prev) if prev[0] is not None else clear()


def per_shard_attention(fn, q: jax.Array, k: jax.Array, v: jax.Array):
    """``fn(q, k, v)`` on [B, H, S, dh] operands, run once per shard under
    ``shard_map``: batch over the DP axes, heads over TP where the query and
    KV head counts both divide it. For kernels XLA cannot partition (Mosaic
    kernels); without an installed context and a current mesh ``fn`` runs
    as is."""
    mesh = jax.sharding.get_abstract_mesh()
    if _DP_AXES is None or mesh.empty:
        return fn(q, k, v)
    dp = 1
    for a in _DP_AXES:
        dp *= mesh.shape[a]
    tp = mesh.shape.get(_TP_AXIS, 0)
    spec = P(_DP_AXES if q.shape[0] % dp == 0 else None,
             _TP_AXIS if tp and q.shape[1] % tp == 0 and k.shape[1] % tp == 0
             else None)
    return jax.shard_map(fn, in_specs=(spec,) * 3, out_specs=spec,
                         check_vma=False)(q, k, v)


def constrain_dims(x: jax.Array, dims: Tuple) -> jax.Array:
    """Generic constraint: ``dims`` entries are 'dp', 'tp', or None per
    leading axis (trailing axes unconstrained). Divisibility-guarded; no-op
    without an installed context (unit tests, single device)."""
    if _DP_AXES is None:
        return x
    spec = []
    for i, d in enumerate(dims[: x.ndim]):
        if d == "dp":
            spec.append(_DP_AXES)
        elif d == "tp":
            spec.append(_TP_AXIS if x.shape[i] % max(1, _TP_SIZE) == 0
                        else None)
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, TypeError):
        return x


def constrain_tokens(x: jax.Array) -> jax.Array:
    """[B, S, D] (or [B, S]) activations: batch over DP; with SP enabled the
    seq dim additionally shards over TP (Megatron-style sequence parallelism
    for the norm/residual regions — XLA turns the boundary into the standard
    all-gather-at-QKV / reduce-scatter-after-Wo pair)."""
    if _DP_AXES is None:
        return x
    if x.ndim == 3:
        seq_ax = (
            _TP_AXIS if (_SP_SEQ and x.shape[1] % max(1, _TP_SIZE) == 0
                         and x.shape[1] >= _TP_SIZE) else None
        )
        spec = P(_DP_AXES, seq_ax, None)
    elif x.ndim == 2:
        spec = P(_DP_AXES, None)
    else:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, TypeError):
        return x  # no mesh context — leave to propagation
