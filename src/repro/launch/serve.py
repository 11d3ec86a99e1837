"""Batched serving through the continuous engine (``launch/engine.py``).

Per-slot positions, an admission queue with per-request deadlines, and slot
refill the moment a request finishes (EOS / ``max_new`` / deadline); design
notes in the README "Serving" section. ``serve`` reports the engine's
counters (``wasted_slot_steps``: slot-steps on free slots) and per-request
TTFT / end-to-end latency percentiles.

CPU-scale demo:  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
    --reduced --requests 6 --slots 2 --max-new 16
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import (ContinuousEngine, Request, request_stats)
from repro.models.model import Model

__all__ = ["Request", "serve"]


def serve(model: Model, params, requests: List[Request], slots: int,
          cap: int) -> Dict:
    """Serve ``requests`` through the continuous engine: its counters, plus
    the wall time, the tokens emitted and the per-request latencies."""
    t0 = time.perf_counter()
    engine = ContinuousEngine(model, params, slots, cap)
    engine.run(requests)
    stats = engine.stats()
    wall = time.perf_counter() - t0
    toks = sum(len(r.out) for r in requests)
    stats.update({"wall_s": wall, "tokens": toks,
                  "tok_per_s": toks / max(wall, 1e-9)})
    stats.update(request_stats(requests))
    return stats


def host_split(stats: Dict) -> Optional[str]:
    """The continuous engine's host time per decode step (blocked on the
    step's tokens, and the rest) and per admission, from its counters, and
    how many of those calls wrote the live cache in place."""
    steps, admits = stats.get("engine_steps"), stats.get("prefills")
    if not steps or not admits:
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
    ap.add_argument("--schedule-db", default=None,
                    help="warm repro.tuna schedule DB (JSONL) so trace-time "
                         "block-spec picks are lookups, not searches")
    ap.add_argument("--schedule-cache", default=None,
                    help="immutable schedule snapshot (python -m repro.tuna "
                         "snapshot); consulted before the DB. Accepts a "
                         "versioned snapshot or a SnapshotManager `latest` "
                         "pointer, loaded once at startup")
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
    stats = serve(model, params, reqs, slots=args.slots, cap=cap)
    print(f"[serve] {stats['tokens']} tokens in "
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
    if args.schedule_cache or os.environ.get("REPRO_TUNA_CACHE"):
        from repro.core import tuner

        cache = tuner.get_default_cache()
        if cache is None:
            print("[serve] schedule cache: none installed (snapshot "
                  "missing or stale)")
        else:
            print(f"[serve] schedule cache: {cache.hits} hits / "
                  f"{cache.misses} misses ({len(cache)} records)")
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
