"""Offline batch serving through ``ContinuousEngine`` of a model whose
weights rule, plain reference and work counts its configuration file
names (``bench``: ``{"weights", "reference", "flops"}``, module names), as
``offline_serving`` serves the dense models with ``bench/weights.py``,
``bench/reference.py`` and ``bench/flops.py``. The work counts go to the
metrics as ``work``.

Set-up, window, traced batch and tally are ``offline_serving``'s. Besides,
the result holds the engine's counters over the traced batch
(``traced_engine``: its MoE counters among them) and the ``moe_gmm``
kernel's calls inside the decode program in the traced window
(``gmm_decode``: calls and device seconds, per device).

The comparison that decides ``correct`` is ``check.serving``'s, with the
configuration's reference: a sample of the finished requests (drawn from
the seed, the longest among them) through the reference, and the gap
between the reference's best logit and the served token's at every served
token; the control (the reference in fp8) goes in the program's place
under ``control``. Two statistics of the gaps are held to the cell's
limits:

- ``max_logit_gap``, the widest, as for the dense cells: it catches a
  token that the reference ranks far down (a broken step);
- ``mean_logit_gap``, the mean over the served tokens. With routed
  experts the widest gap cannot tell bfloat16 from a lower precision:
  routing near-ties at the k-th expert flip between bfloat16 and float32
  and the flip grows through the layers, so a few tokens of a sound run
  read as wide a gap as the fp8 control's widest. The flips are rare, so
  the mean separates the two.
"""
from __future__ import annotations

import functools
import gc
import importlib
import math
import os
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from bench import check as check_mod
from bench import trace as trace_mod
from bench.common import CompileClock, arch_config
from bench.runners.offline_serving import (_Recorder, _requests, _tally,
                                           _traced_batch, _warm)
from bench.traffic import Traffic

ROWS = 256  # logit rows per call of the head


def _modules(config: Dict):
    return {k: importlib.import_module(v) for k, v in config["bench"].items()}


def gmm_decode(events: Dict):
    """(calls, device seconds) of the ``moe_gmm`` kernel inside the decode
    program, in the traced window, averaged over the devices."""
    w = trace_mod._window(events)
    n = s_ = 0.0
    for dev in events["devices"]:
        mods = sorted((s, s + d, trace_mod.program_name(m))
                      for m, s, d in dev["modules"] if trace_mod._in(s, w))
        starts = [a for a, _, _ in mods]
        for text, s, d in dev["ops"]:
            if (trace_mod._in(s, w)
                    and trace_mod.short_name(text).startswith("moe_gmm")
                    and trace_mod._at(starts, mods, s) == "jit_decode_step"):
                n += 1
                s_ += d
    k = max(len(events["devices"]), 1)
    return n / k, s_ / k


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, fault=None, control: bool = False,
        keep_trace: Optional[str] = None) -> Dict:
    import jax

    from repro.launch.engine import ContinuousEngine
    from repro.models.model import Model

    device = devices[0]
    mods = _modules(cell.config)
    cfg = arch_config(cell.config)
    traffic = Traffic(cell.traffic, cfg.vocab)
    model = Model(cfg)
    if fault is not None:
        model = fault(model)
    params = jax.block_until_ready(mods["weights"].make_params(
        cfg, jax.eval_shape(model.init, jax.random.key(0)), seed))
    engine = ContinuousEngine(model, params, traffic.slots, traffic.cap)
    rec = _Recorder(engine)
    clock = CompileClock()
    _warm(engine, traffic)
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.count
    counters0 = engine.stats()

    rec.decodes.clear()
    rec.prefills.clear()
    batches = []
    traced = None
    w0 = time.perf_counter()
    w1 = w0 + seconds
    index = 0
    while time.perf_counter() < w1:
        reqs = _requests(traffic, seed, index, w1 - time.perf_counter())
        if trace and index == 0:
            before = engine.stats()
            with tempfile.TemporaryDirectory(prefix="bench-arch-") as tmp:
                t = _traced_batch(engine, reqs, keep_trace or tmp)
                events = trace_mod.load(os.path.join(keep_trace or tmp,
                                                     "trace.xplane.pb"))
            after = engine.stats()
            traced = {"trace": t, "traced_decodes": list(rec.decodes),
                      "traced_prefills": list(rec.prefills),
                      "traced_engine": {k: after[k] - before[k] for k in after},
                      "gmm_decode": gmm_decode(events)}
            del events
        else:
            with jax.profiler.TraceAnnotation("bench.batch"):
                engine.run(reqs)
        batches.append((engine._t0, reqs))
        index += 1
    window_compiles = clock.count - compiles0
    counters = {k: engine.stats()[k] - counters0[k] for k in counters0}
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))

    served = _tally(batches, w1)
    print(f"[bench] window: {served['tokens_in_window']} tokens, "
          f"{served['attempted']} requests admitted, "
          f"{len(served['tpot_ms'])} finished (the tpot sample)", file=sys.stderr)
    result = {"cfg": cfg, "work": mods["flops"], "seconds": seconds,
              "setup_s": setup_s,
              "engine": counters, "window_compiles": window_compiles,
              "memory_peak_bytes": peak, **served}
    if traced is not None:
        result.update(traced)
        t = traced["trace"]
        print(f"[bench] traced the first batch whole, {t['window_s']} s: "
              f"{len(traced['traced_decodes'])} decode steps launched, "
              f"{t['programs'].get('jit_decode_step', (0, 0.0))[0]} in the "
              f"trace; {traced['gmm_decode'][0]} moe_gmm calls in them",
              file=sys.stderr)

    finished = [r for _, reqs in batches for r in reqs
                if r.finished() and not r.truncated]
    del engine, params, rec
    gc.collect()
    result["checks"], result["readings"] = serving(
        mods, cfg, seed, finished, traffic, cell.limits, control=control)
    return result


@functools.lru_cache(maxsize=None)
def _chunk_fn(ref, cfg, control: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(head, h, served, valid, hc):
        """(widest, summed) gap of the served tokens, and of the control's
        first-ranked ones."""
        logits = ref.final_logits(cfg, head, h)
        best = logits.max(-1)

        def stats(pick):
            gap = best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
            gap = jnp.where(valid, gap, 0.0)
            return gap.max(), gap.sum()

        out = stats(served)
        if control:
            return out, stats(ref.final_logits(cfg, head, hc, "fp8").argmax(-1))
        return out, out

    return f


def gaps(mods, cfg, seed: int, requests, control: bool = False):
    """((widest, mean) gap of the served tokens, (widest, mean) gap of the
    control's first-ranked tokens, served tokens compared), as
    ``check.gaps`` reads them, over sequences padded to a multiple of 128
    (not to the cache's capacity: the reference's cost grows with the
    length)."""
    import jax.numpy as jnp

    ref, W = mods["reference"], mods["weights"]
    n = len(requests)
    seqs = [list(r.prompt) + list(r.out[:-1]) for r in requests]
    length = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((n, length), np.int32)
    rows, served = [], []
    for j, (r, seq) in enumerate(zip(requests, seqs)):
        tokens[j, :len(seq)] = seq
        rows += [j * length + len(r.prompt) - 1 + k for k in range(len(r.out))]
        served += list(r.out)
    h = ref.hidden_from_seed(cfg, seed, jnp.asarray(tokens)).reshape(n * length, -1)
    hc = (ref.hidden_from_seed(cfg, seed, jnp.asarray(tokens), "fp8")
          .reshape(n * length, -1) if control else h)
    head = ref.head_from_seed(cfg, W.seed_key(seed))
    f = _chunk_fn(ref, cfg, control)
    widest, total = [-math.inf, -math.inf], [0.0, 0.0]
    for i in range(0, len(rows), ROWS):
        idx = np.zeros(ROWS, np.int32)
        tok = np.zeros(ROWS, np.int32)
        valid = np.zeros(ROWS, bool)
        m = len(rows[i:i + ROWS])
        idx[:m], tok[:m], valid[:m] = rows[i:i + ROWS], served[i:i + ROWS], True
        for j, (g, s) in enumerate(f(head, h[idx], jnp.asarray(tok),
                                     jnp.asarray(valid), hc[idx])):
            widest[j], total[j] = max(widest[j], float(g)), total[j] + float(s)
    n = len(rows)
    return (widest[0], total[0] / n), (widest[1], total[1] / n), n


def serving(mods, cfg, seed: int, finished, traffic, limits: Dict,
            control: bool = False):
    """``check.serving`` with the configuration's reference."""
    picked = check_mod.sample(finished, seed, int(traffic.spec["check"]["sample"]))
    if picked:
        gap, cgap, n = gaps(mods, cfg, seed, picked, control=control)
    else:
        gap = cgap = (math.inf, math.inf)
        n = 0
    print(f"[bench] compared {n} served tokens of {len(picked)} requests "
          f"(longest {len(picked[0].out) if picked else 0} tokens) with the "
          "float32 reference", file=sys.stderr)
    readings = {"program": gap[0], "program_mean": gap[1], "tokens": n}
    if control:
        readings.update(control=cgap[0], control_mean=cgap[1])
        gap = cgap
    checks = {name: {"value": v if math.isfinite(v) else None,  # None: nothing
                     "limit": limits[name]}                      # to compare
              for name, v in zip(("max_logit_gap", "mean_logit_gap"), gap)}
    return checks, readings
