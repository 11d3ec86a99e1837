"""Jit-friendly kernel entry points with Tuna-tuned schedules.

``matmul`` / ``attention`` dispatch between the Pallas TPU kernels and the
jnp reference paths:

* on a TPU backend → the compiled Pallas kernel, with block sizes tuned
  statically for the target that ``hw.DEVICE_KINDS`` maps the device to
  (an unknown device kind is an error, never a default);
* on any other backend → the jnp oracle, unless ``target`` names a
  hardware target: then the Pallas kernel runs in interpret mode with
  blocks tuned for that target (tests and CPU benchmarks do this).

Tuning happens at trace time via ``core.tuner`` — pure static analysis, no
device execution, memoised per shape (the paper's compilation-service flow).
Both block-spec pickers consult the golden kernel bundle first
(``use_kernel_bundle(path)`` or ``$REPRO_TUNA_BUNDLE``), then the serving
snapshot cache (``use_schedule_cache(path)`` or ``$REPRO_TUNA_CACHE``), and
then the warm ``repro.tuna`` schedule DB (``use_schedule_db(path)`` or
``$REPRO_TUNA_DB``): on a warm store, trace time pays a dict lookup, not a
search.

A loaded kernel bundle serves more than block specs: a Pallas-path call on
*concrete* arrays whose (kernel, shapes, dtype, semantic knobs) match a
bundled AOT executable skips trace+lower+compile entirely and runs the
deserialized executable — zero Pallas compilations at serve cold-start
(``pallas_trace_counts`` is the witness; ``benchmarks/cold_start.py``
measures it). Calls under an outer ``jit`` see tracers and fall through to
the ordinary trace path — an AOT executable cannot be inlined into someone
else's trace.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import op_registry, tuner
from repro.core.tuner import tuned_matmul_blocks
from repro.hw import resolve_target
from repro.kernels import ref
from repro.kernels import flash_attention as _flash_mod
from repro.kernels import matmul as _matmul_mod
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_schedule_db(path) -> None:
    """Point the kernel block-spec pickers at a warm schedule database."""
    tuner.set_default_db(path)  # clears all registered block-spec memos


def use_schedule_cache(path) -> None:
    """Serve block-spec picks from an immutable snapshot (``python -m
    repro.tuna snapshot``) — consulted before the DB, O(1) and lock-free."""
    tuner.set_default_cache(path)  # clears all registered block-spec memos


def refresh_schedule_cache() -> bool:
    """Hot-swap the installed snapshot if it was republished (revalidated
    by the snapshot's content digest, not file stat). Clears the block-spec
    memos on swap so already-traced shapes re-resolve; True iff swapped."""
    return tuner.refresh_default_cache()


def use_kernel_bundle(bundle) -> None:
    """Install a golden kernel bundle (``python -m repro.tuna golden
    --bundle``): a path (or ``latest`` pointer), a loaded
    ``repro.tuna.golden.KernelBundle``, or ``None`` to switch OFF. The
    bundle becomes the first schedule-lookup tier (before snapshot cache
    and DB), and Pallas-path calls on concrete arrays matching a bundled
    executable run ahead-of-time compiled code — no trace, no compile."""
    tuner.set_default_bundle(bundle)  # clears all block-spec memos


def get_kernel_bundle():
    """The installed ``KernelBundle`` (or None) — resolved through
    ``core.tuner`` so there is exactly one process-wide bundle."""
    return tuner.get_default_bundle()


def pallas_trace_counts() -> Dict[str, int]:
    """How many times each Pallas kernel family has been traced/built in
    this process — the zero-compile acceptance witness for bundled serving
    (an AOT executable served from the bundle never re-enters the kernel
    builders, so these stay flat)."""
    return {"matmul": _matmul_mod.TRACE_COUNT,
            "flash": _flash_mod.TRACE_COUNT}


def reset_pallas_trace_counts() -> None:
    _matmul_mod.TRACE_COUNT = 0
    _flash_mod.TRACE_COUNT = 0


def _bundle_executable(kernel: str, args, params: Optional[Dict] = None):
    """The installed bundle's AOT executable for this concrete call, or
    None. Tracers (an outer jit's abstract values) always miss: a
    serialized executable is a leaf computation, callable only on real
    arrays from op-by-op dispatch."""
    bundle = tuner.get_default_bundle()
    if bundle is None:
        return None
    if any(isinstance(a, jax.core.Tracer) for a in args):
        return None
    return bundle.executable(kernel, args, params)


@functools.lru_cache(maxsize=256)
def tuned_flash_blocks(
    s: int, d: int, dtype_bytes: int = 2, target_name: Optional[str] = None
) -> Tuple[int, int]:
    """Static block_q/block_k choice for flash attention: score the induced
    (q·kᵀ then p·v) tile working set over the registry's ``flash`` space
    (whose knobs are exactly this kernel's grid). ``target_name=None``
    tunes for the device JAX runs on (``hw.resolve_target``)."""
    target = resolve_target(target_name)
    db = tuner.get_default_db()
    space = op_registry.make_space(
        "flash", {"s": s, "d": d, "dtype_bytes": dtype_bytes}, target.kind)
    sig = space.signature()
    rec = tuner.lookup_best(sig, target.name)  # snapshot cache, then DB
    if rec is not None:
        return rec.config["block_q"], rec.config["block_k"]
    best = (None, float("inf"))
    evals = 0
    for cfg in space.enumerate(None):
        bq, bk_ = cfg["block_q"], cfg["block_k"]
        evals += 1
        # tile working set: q, k, v, acc + softmax stats, double-buffered
        vmem = (bq * d + 2 * bk_ * d + bq * d) * dtype_bytes + bq * (
            2 * 128 + bk_
        ) * 4
        if 2 * vmem > target.fast_mem_bytes:
            continue
        # per-step MXU work: bq×bk×d + bq×d×bk
        tiles = (bq // 128 or 1) * (bk_ // 128 or 1) * max(1, d // 128)
        dma = (bq * d + 2 * bk_ * d) * dtype_bytes
        t = 2 * tiles * 20 / target.clock_hz + dma / target.hbm_bandwidth
        # prefer larger tiles (fewer grid steps / revisits) on ties
        steps = (s // bq) * (s // bk_)
        score = t * steps
        if score < best[1]:
            best = ((bq, bk_), score)
    blocks = best[0] or (min(512, s), min(512, s))
    if tuner._writable(db) and best[0] is not None:
        from repro.tuna.db import ScheduleRecord

        db.add(ScheduleRecord(
            op=sig, target=target.name,
            config={"block_q": blocks[0], "block_k": blocks[1]},
            score=best[1],
            evaluations=evals,
            meta={"strategy": "flash_grid"},
        ))
    return blocks


# set_default_db must invalidate this memo too (it lives here, not in
# core.tuner, because importing kernels pulls in jax)
tuner.register_memo_clearer(tuned_flash_blocks.cache_clear)


def matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    blocks: Optional[Tuple[int, int, int]] = None,
    target: Optional[str] = None,
) -> jax.Array:
    """Tuna-tuned blocked matmul (see the module docstring for dispatch)."""
    m, k = x.shape
    _, n = y.shape
    on_tpu = _on_tpu()
    if not (on_tpu or target):
        return ref.matmul(x, y)
    if blocks is None:
        fn = _bundle_executable("matmul", (x, y))
        if fn is not None:
            return fn(x, y)
        blocks = tuned_matmul_blocks(m, n, k, x.dtype.itemsize, target)
    bm, bn, bk = blocks
    return matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, interpret=not on_tpu)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    blocks: Optional[Tuple[int, int]] = None,
    target: Optional[str] = None,
) -> jax.Array:
    """Tuna-tuned flash attention (see the module docstring for dispatch)."""
    on_tpu = _on_tpu()
    if not (on_tpu or target):
        return ref.attention(q, k, v, causal=causal, scale=scale)
    s, d = q.shape[-2], q.shape[-1]
    if blocks is None:
        fn = _bundle_executable(
            "flash", (q, k, v),
            {"causal": causal,
             "scale": scale if scale is not None else d ** -0.5})
        if fn is not None:
            return fn(q, k, v)
        blocks = tuned_flash_blocks(s, d, q.dtype.itemsize, target)
    bq, bk = blocks
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale, block_q=bq, block_k=bk,
        interpret=not on_tpu,
    )
