"""Serve cold-start: first-call latency with vs without an AOT kernel bundle.

The paper's Table II metric (``benchmarks/compile_time.py``) measures what
*tuning* costs; this benchmark measures what a serving process pays on its
first request — the Pallas trace + lower + compile of every kernel it is
about to run — and what remains of that cost when the kernels arrive as a
golden release's ahead-of-time compiled bundle (``python -m repro.tuna
golden --bundle``).

Method: tune the benchmark shapes into an in-memory store, promote them to
a golden release, build the bundle, then time two cold starts per
iteration, each from a cleared jax compilation cache and cold block-spec
memos:

* **unbundled** — warm *schedule* snapshot installed (block-spec picks are
  O(1) lookups in both runs, so the delta is compilation, not search),
  first ``ops.matmul`` + ``ops.attention`` call pays the full Pallas
  trace+compile;
* **bundled** — ``ops.use_kernel_bundle`` (bundle load + executable
  deserialization timed as part of the cold start, because it is), first
  calls dispatch to the deserialized executables.

Both runs use identical block configs, so outputs are comparable
bit-for-bit. ``--check`` exits 1 unless the bundled cold start is strictly
faster, performed **zero** Pallas traces (``kernels.ops
.pallas_trace_counts``), and matched the unbundled outputs. Emits
``BENCH_compile.json``, folding in ``compile_time_comparison`` so the
tune-time and serve-time halves of the story live in one artifact:

    PYTHONPATH=src python -m benchmarks.cold_start --check \
        --out BENCH_compile.json
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tuner
from repro.kernels import ops

PARITY_ATOL = 2e-4  # f32 + identical blocks: expected 0.0, tolerance for
#                     backend-revision drift in reduction order


def _sync(x) -> None:
    np.asarray(x)  # host transfer = execution barrier, interpret-safe


def _tune_records(M: int, S: int, D: int):
    """Tune the benchmark shapes into a fresh in-memory store and return
    its records — real tuner output, so the golden release the bundle is
    built from carries the exact configs the unbundled run would pick."""
    from repro.tuna.db import ScheduleDatabase

    db = ScheduleDatabase()
    tuner.set_default_db(db)
    try:
        tuner.tuned_matmul_blocks(M, M, M, 4, "tpu_v5e")
        ops.tuned_flash_blocks(S, D, 4, "tpu_v5e")
    finally:
        tuner.set_default_db(None)
    return db.records()


def _cold_state() -> None:
    """Per-measurement reset: compiled-computation cache, block-spec
    memos, and the Pallas trace counters all back to process-start."""
    jax.clear_caches()
    tuner._clear_memos()
    ops.reset_pallas_trace_counts()


def run_benchmark(M: int = 256, S: int = 128, D: int = 64,
                  iters: int = 3, seed: int = 0,
                  ct_configs: int = 8, ct_iters: int = 2,
                  workdir: str = None) -> Dict:
    from repro.tuna.cache import ScheduleCache
    from repro.tuna.golden import GoldenManager, build_kernel_bundle

    workdir = workdir or tempfile.mkdtemp(prefix="tuna_cold_start_")
    records = _tune_records(M, S, D)
    mgr = GoldenManager(workdir)
    info = mgr.promote(records, "tpu_v5e", source="benchmarks/cold_start")
    _, release = mgr.load_release(info.path)
    t0 = time.perf_counter()
    bundle_info = build_kernel_bundle(release, workdir, "tpu_v5e",
                                      golden_name=info.name)
    bundle_build_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M, M)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((M, M)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((1, 1, S, D)), jnp.float32)

    snapshot = ScheduleCache(records, source="cold_start")

    unbundled = {"wall_s": [], "matmul_s": [], "flash_s": []}
    bundled = {"wall_s": [], "load_s": [], "matmul_s": [], "flash_s": []}
    out_u = out_b = att_u = att_b = None
    traces_u = traces_b = None

    for _ in range(iters):
        # -- unbundled cold start (warm snapshot, cold compiler) ----------
        ops.use_kernel_bundle(None)
        tuner.set_default_cache(snapshot)
        _cold_state()
        t0 = time.perf_counter()
        out_u = ops.matmul(x, y, target="tpu_v5e")
        _sync(out_u)
        t1 = time.perf_counter()
        att_u = ops.attention(q, q, q, target="tpu_v5e")
        _sync(att_u)
        t2 = time.perf_counter()
        traces_u = ops.pallas_trace_counts()
        unbundled["matmul_s"].append(t1 - t0)
        unbundled["flash_s"].append(t2 - t1)
        unbundled["wall_s"].append(t2 - t0)

        # -- bundled cold start (load timed in: it is the cold path) ------
        tuner.set_default_cache(None)
        ops.use_kernel_bundle(None)  # drop the deserialized-executable memo
        _cold_state()
        t0 = time.perf_counter()
        ops.use_kernel_bundle(bundle_info.path)
        t_load = time.perf_counter()
        out_b = ops.matmul(x, y, target="tpu_v5e")
        _sync(out_b)
        t1 = time.perf_counter()
        att_b = ops.attention(q, q, q, target="tpu_v5e")
        _sync(att_b)
        t2 = time.perf_counter()
        traces_b = ops.pallas_trace_counts()
        bundled["load_s"].append(t_load - t0)
        bundled["matmul_s"].append(t1 - t_load)
        bundled["flash_s"].append(t2 - t1)
        bundled["wall_s"].append(t2 - t0)
        ops.use_kernel_bundle(None)

    max_diff = float(max(
        np.abs(np.asarray(out_u) - np.asarray(out_b)).max(),
        np.abs(np.asarray(att_u) - np.asarray(att_b)).max()))
    best_u = min(unbundled["wall_s"])
    best_b = min(bundled["wall_s"])
    from benchmarks.compile_time import compile_time_comparison

    result = {
        "schema": "bench-compile-v1",
        "shapes": {"matmul": [M, M, M], "flash": [1, 1, S, D],
                   "dtype": "float32"},
        "iters": iters,
        "cold_start": {
            "unbundled": {
                "wall_s": best_u,
                "matmul_s": min(unbundled["matmul_s"]),
                "flash_s": min(unbundled["flash_s"]),
                "all_wall_s": unbundled["wall_s"],
                "pallas_traces": traces_u,
            },
            "bundled": {
                "wall_s": best_b,
                "bundle_load_s": min(bundled["load_s"]),
                "matmul_s": min(bundled["matmul_s"]),
                "flash_s": min(bundled["flash_s"]),
                "all_wall_s": bundled["wall_s"],
                "pallas_traces": traces_b,
            },
            "speedup": best_u / max(best_b, 1e-9),
            "parity": {"ok": max_diff <= PARITY_ATOL,
                       "max_abs_diff": max_diff},
        },
        "bundle": {
            "name": bundle_info.name,
            "entries": bundle_info.entries,
            "schedules": bundle_info.schedules,
            "build_s": bundle_build_s,
            "golden": info.name,
        },
        "compile_time_comparison": compile_time_comparison(
            n_configs=ct_configs, iters=ct_iters, seed=seed),
    }
    return result


def check(result: Dict) -> list:
    """Acceptance gates; returns the list of violated ones (empty = pass)."""
    cs = result["cold_start"]
    bad = []
    if not cs["parity"]["ok"]:
        bad.append(f"bundled outputs diverge from unbundled "
                   f"(max_abs_diff={cs['parity']['max_abs_diff']:.2e})")
    traces = cs["bundled"]["pallas_traces"]
    if any(traces.values()):
        bad.append(f"bundled cold start traced Pallas kernels: {traces} "
                   f"(must be zero — the bundle exists so it doesn't)")
    if sum(cs["unbundled"]["pallas_traces"].values()) < 2:
        bad.append(f"unbundled cold start did not trace both kernels "
                   f"({cs['unbundled']['pallas_traces']}) — the baseline "
                   f"is not measuring compilation")
    if cs["bundled"]["wall_s"] >= cs["unbundled"]["wall_s"]:
        bad.append(f"bundled cold start not strictly faster: "
                   f"{cs['bundled']['wall_s']:.4f}s vs "
                   f"{cs['unbundled']['wall_s']:.4f}s")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_compile.json")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the bundled cold start is strictly "
                         "faster, traced zero Pallas kernels, and matched "
                         "the unbundled outputs")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--matmul", type=int, default=256, metavar="M",
                    help="square matmul dimension")
    ap.add_argument("--seq", type=int, default=128, help="flash seq length")
    ap.add_argument("--head", type=int, default=64, help="flash head dim")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ct-configs", type=int, default=8,
                    help="candidate count for the folded-in "
                         "compile_time_comparison")
    ap.add_argument("--workdir", default=None,
                    help="where the golden release + bundle land (default: "
                         "a temp dir)")
    args = ap.parse_args()

    result = run_benchmark(M=args.matmul, S=args.seq, D=args.head,
                           iters=args.iters, seed=args.seed,
                           ct_configs=args.ct_configs, workdir=args.workdir)
    cs = result["cold_start"]
    print(f"[bench_compile] unbundled cold start: "
          f"{cs['unbundled']['wall_s']*1e3:.1f}ms "
          f"(traces {cs['unbundled']['pallas_traces']})")
    print(f"[bench_compile] bundled cold start:   "
          f"{cs['bundled']['wall_s']*1e3:.1f}ms "
          f"(load {cs['bundled']['bundle_load_s']*1e3:.1f}ms, "
          f"traces {cs['bundled']['pallas_traces']})")
    print(f"[bench_compile] speedup {cs['speedup']:.2f}x, parity "
          f"max|diff|={cs['parity']['max_abs_diff']:.2e}, bundle "
          f"{result['bundle']['entries']} kernels "
          f"built in {result['bundle']['build_s']:.2f}s")
    ct = result["compile_time_comparison"]
    print(f"[bench_compile] tune-time (Table II, {ct['n_configs']} cfgs): "
          f"static {ct['static_s']:.3f}s vs dynamic {ct['dynamic_s']:.3f}s "
          f"({ct['speedup']:.0f}x)")
    from benchmarks.bench_json import write_bench

    write_bench(result, args.out)
    print(f"[bench_compile] wrote {args.out}")
    if args.check:
        bad = check(result)
        for msg in bad:
            print(f"[bench_compile] CHECK FAILED: {msg}", file=sys.stderr)
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
