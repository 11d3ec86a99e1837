"""Batched serving driver: continuous batching (default) or wave fallback.

Requests flow through one of two schedulers (design notes: README
"Serving" section — slot lifecycle, per-slot positions, refill
invariants):

* ``continuous`` (default) — ``launch/engine.py``: per-slot position
  vectors, an admission queue with per-request deadlines, and slot refill
  the moment a request finishes (EOS / ``max_new`` / deadline). Schedule
  snapshots hot-reload at admission boundaries.
* ``wave`` (fallback, for parity comparison) — waves of ``slots`` equal-
  length prompts prefill *batched* (the prefill path the dry-run lowers at
  32k) then decode in lockstep with a scalar position; a finished request
  parks its slot until the wave drains. Snapshot polls land between waves.

Both report per-request TTFT / end-to-end latency percentiles and
``wasted_slot_steps`` (slot-steps burned on pad/finished slots) so the
schedulers compare honestly — see ``benchmarks/serving_latency.py``.

CPU-scale demo:  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
    --reduced --requests 6 --slots 2 --max-new 16
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import (ContinuousEngine, Request, request_stats)
from repro.models.model import Model

__all__ = ["Request", "ServeEngine", "serve", "group_into_waves"]


def group_into_waves(requests: List[Request], slots: int) -> List[List[Request]]:
    """Bucket by prompt length (wave prefill is one batched launch, so a
    wave must be homogeneous), then chunk each bucket into waves of at most
    ``slots``. Submission order is preserved within a bucket; short tail
    waves get padded at run time — the honest cost the accounting exposes."""
    buckets: Dict[int, List[Request]] = {}
    for r in requests:
        buckets.setdefault(len(r.prompt), []).append(r)
    waves = []
    for length in buckets:
        group = buckets[length]
        waves.extend(group[i: i + slots] for i in range(0, len(group), slots))
    return waves


class ServeEngine:
    """Lockstep wave scheduler (the fallback baseline)."""

    def __init__(self, model: Model, params, slots: int, cap: int):
        self.model = model
        self.params = params
        self.slots = slots
        self.cap = cap
        self._prefill = jax.jit(lambda p, b: model.prefill(p, b, cap))
        # the cache is donated: each step writes its tokens in place
        self._decode = jax.jit(model.decode_step, donate_argnums=1)
        self.engine_steps = 0        # decode launches
        self.slot_steps = 0          # slot-steps doing live work
        self.wasted_slot_steps = 0   # slot-steps on pad/finished slots
        self.prefills = 0
        self._t0 = time.perf_counter()

    def run_wave(self, wave: List[Request]) -> None:
        assert len({len(r.prompt) for r in wave}) == 1, "wave = equal prompts"
        n = len(wave)
        prompts = np.array([r.prompt for r in wave], np.int32)
        if n < self.slots:  # pad to engine width
            prompts = np.pad(prompts, ((0, self.slots - n), (0, 0)))
        batch = {"tokens": jnp.asarray(prompts)}
        cache, pos, last_logits = self._prefill(self.params, batch)
        self.prefills += 1
        tok = jnp.argmax(last_logits[:, 0], axis=-1).astype(jnp.int32)
        tok_np = np.asarray(tok)  # one host sync per step, not one per slot
        now = time.perf_counter() - self._t0
        for i, r in enumerate(wave):
            r.out.append(int(tok_np[i]))
            r.t_first = now
            if len(r.out) >= r.max_new:
                r.t_done = now
        max_new = max(r.max_new for r in wave)
        for t in range(max_new - 1):
            # pad rows (slots - n) and already-finished requests still run
            # the full decode step — that is the wave scheduler's cost; it
            # is *reported* as waste, never as engine work
            live = sum(1 for r in wave if len(r.out) < r.max_new)
            logits, cache = self._decode(self.params, cache, tok, pos + t)
            self.engine_steps += 1
            self.slot_steps += live
            self.wasted_slot_steps += self.slots - live
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok_np = np.asarray(tok)
            now = time.perf_counter() - self._t0
            for i, r in enumerate(wave):
                if len(r.out) < r.max_new:
                    r.out.append(int(tok_np[i]))
                    if len(r.out) >= r.max_new:
                        r.t_done = now


def serve(model: Model, params, requests: List[Request], slots: int,
          cap: int, refresh=None, scheduler: str = "continuous") -> Dict:
    """Serve ``requests`` with the chosen scheduler.

    ``refresh`` (nullary, returns True on change) is the schedule-snapshot
    hot-reload hook: a fleet republish lands in a long-running serve
    process with no restart. The wave scheduler polls it *between* waves
    (never mid-wave); the continuous engine polls at *admission*
    boundaries — the moment a new request enters the engine.
    """
    t0 = time.perf_counter()
    if scheduler == "continuous":
        engine = ContinuousEngine(model, params, slots, cap, refresh=refresh)
        engine.run(requests)
        stats = engine.stats()
    elif scheduler == "wave":
        engine = ServeEngine(model, params, slots, cap)
        reloads = 0
        for i, wave in enumerate(group_into_waves(requests, slots)):
            if refresh is not None and i and refresh():
                reloads += 1
            engine.run_wave(wave)
        stats = {"engine_steps": engine.engine_steps,
                 "slot_steps": engine.slot_steps,
                 "wasted_slot_steps": engine.wasted_slot_steps,
                 "prefills": engine.prefills,
                 "cache_reloads": reloads}
    else:
        raise ValueError(f"unknown scheduler: {scheduler!r}")
    wall = time.perf_counter() - t0
    toks = sum(len(r.out) for r in requests)
    stats.update({"scheduler": scheduler, "wall_s": wall, "tokens": toks,
                  "tok_per_s": toks / max(wall, 1e-9)})
    stats.update(request_stats(requests))
    return stats


def host_split(stats: Dict) -> Optional[str]:
    """The continuous engine's host time per decode step (blocked on the
    step's tokens, and the rest) and per admission, from its counters, and
    how many of those calls wrote the live cache in place."""
    steps, admits = stats.get("engine_steps"), stats.get("prefills")
    if "sync_s" not in stats or not steps or not admits:
        return None
    return (f"per decode step: sync {1e3 * stats['sync_s'] / steps:.2f} ms, "
            f"host {1e3 * (stats['launch_s'] + stats['emit_s']) / steps:.2f} "
            f"ms; per admission {1e3 * stats['admit_s'] / admits:.2f} ms; "
            f"cache in place in {stats['cache_in_place']} of "
            f"{steps + admits} calls")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--scheduler", choices=("continuous", "wave"),
                    default="continuous",
                    help="continuous = per-slot positions + refill on free; "
                         "wave = lockstep fallback for parity comparison")
    ap.add_argument("--schedule-db", default=None,
                    help="warm repro.tuna schedule DB (JSONL) so trace-time "
                         "block-spec picks are lookups, not searches")
    ap.add_argument("--schedule-cache", default=None,
                    help="immutable schedule snapshot (python -m repro.tuna "
                         "snapshot); consulted before the DB — the lock-free "
                         "serving hot path. Accepts a versioned snapshot or "
                         "a SnapshotManager `latest` pointer; polled at "
                         "admission/wave boundaries, so a republish lands "
                         "without restart")
    ap.add_argument("--no-schedule-refresh", action="store_true",
                    help="do not poll the snapshot while serving (pin the "
                         "instance loaded at startup)")
    ap.add_argument("--kernel-bundle", default=None,
                    help="golden AOT kernel bundle (python -m repro.tuna "
                         "golden --bundle, or its `latest` pointer): the "
                         "first schedule-lookup tier, plus ahead-of-time "
                         "compiled executables so cold start performs zero "
                         "Pallas compilations for bundled kernels")
    args = ap.parse_args()

    if args.schedule_db:
        from repro.kernels.ops import use_schedule_db

        use_schedule_db(args.schedule_db)
    if args.schedule_cache:
        from repro.kernels.ops import use_schedule_cache

        use_schedule_cache(args.schedule_cache)
    if args.kernel_bundle:
        from repro.kernels.ops import use_kernel_bundle

        use_kernel_bundle(args.kernel_bundle)
        from repro.core import tuner as _tuner

        print(f"[serve] kernel bundle: {_tuner.get_default_bundle().describe()}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    reqs = [
        Request(i, list(rng.integers(0, cfg.vocab, args.prompt_len)),
                args.max_new)
        for i in range(args.requests)
    ]
    cap = args.prompt_len + args.max_new + 2
    # --schedule-cache or $REPRO_TUNA_CACHE both install a snapshot; either
    # way the serve loop polls for republishes (a stale/unbuilt env
    # snapshot resolves to OFF at startup and *heals* through the poll)
    import os

    cache_installed = bool(args.schedule_cache
                           or os.environ.get("REPRO_TUNA_CACHE"))
    refresh = None
    if cache_installed and not args.no_schedule_refresh:
        from repro.core import tuner

        def refresh():
            swapped = tuner.refresh_default_cache()
            if swapped:
                print("[serve] schedule snapshot republish observed — "
                      "hot-reloaded (hit counters reset)")
            return swapped

    stats = serve(model, params, reqs, slots=args.slots, cap=cap,
                  refresh=refresh, scheduler=args.scheduler)
    print(f"[serve] {stats['scheduler']}: {stats['tokens']} tokens in "
          f"{stats['wall_s']:.2f}s ({stats['tok_per_s']:.1f} tok/s, "
          f"{stats['engine_steps']} engine steps, "
          f"{stats['slot_steps']} live slot-steps, "
          f"{stats['wasted_slot_steps']} wasted)")
    print(f"[serve] ttft p50/p95/p99 = {stats['ttft_s']['p50']:.3f}/"
          f"{stats['ttft_s']['p95']:.3f}/{stats['ttft_s']['p99']:.3f}s; "
          f"latency p50/p95/p99 = {stats['latency_s']['p50']:.3f}/"
          f"{stats['latency_s']['p95']:.3f}/{stats['latency_s']['p99']:.3f}s")
    split = host_split(stats)
    if split:
        print(f"[serve] {split}")
    if cache_installed:
        from repro.core import tuner

        cache = tuner.get_default_cache()
        if cache is None:
            print("[serve] schedule cache: none installed (snapshot "
                  "missing or stale; republish to hot-load it)")
        else:
            print(f"[serve] schedule cache: {cache.hits} hits / "
                  f"{cache.misses} misses ({len(cache)} records, "
                  f"{stats['cache_reloads']} hot reloads)")
    if args.kernel_bundle:
        from repro.core import tuner
        from repro.kernels.ops import pallas_trace_counts

        bundle = tuner.get_default_bundle()
        traces = pallas_trace_counts()
        print(f"[serve] kernel bundle: {bundle.hits} schedule hits, "
              f"{bundle.exec_hits} AOT executable hits / "
              f"{bundle.exec_misses} misses; pallas traces this process: "
              f"matmul={traces['matmul']} flash={traces['flash']}")


if __name__ == "__main__":
    main()
