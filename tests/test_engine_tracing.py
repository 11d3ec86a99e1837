"""The continuous engine's host spans and host-time counters, and the names
of its programs, on the CPU at the reduced yi width."""
import copy
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace as T
from repro.configs.base import get_config
from repro.launch.engine import (ContinuousEngine, Request,
                                 greedy_decode_reference)
from repro.launch.serve import host_split, serve
from repro.models.model import Model

SLOTS, SPEC = 2, [(4, 3), (8, 6), (4, 1), (12, 4), (8, 5)]
CAP = max(p + m for p, m in SPEC) + 2
PHASES = ("admit_s", "launch_s", "sync_s", "emit_s")

# each span of the engine's loop, and the span it opens inside
PARENT = {"engine.step": None, "engine.admit": "engine.step",
          "engine.prefill": "engine.admit",
          "engine.first_token": "engine.admit",
          "engine.insert": "engine.admit", "engine.launch": "engine.step",
          "engine.sync": "engine.step", "engine.emit": "engine.step"}
PER_REQUEST = ("engine.prefill", "engine.first_token", "engine.insert")


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("yi_6b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def _requests(vocab, spec=SPEC):
    rng = np.random.default_rng(3)
    return [Request(i, rng.integers(0, vocab, p).tolist(), m)
            for i, (p, m) in enumerate(spec)]


def _engine(model, params):
    return ContinuousEngine(model, params, SLOTS, CAP)


@pytest.fixture(scope="module")
def traced(model_and_params, tmp_path_factory):
    """One run of the engine under the profiler: its requests and the
    engine's spans, each (name, start, end, metadata)."""
    cfg, model, params = model_and_params
    engine = _engine(model, params)
    engine.run(_requests(cfg.vocab))  # compile outside the trace
    reqs = _requests(cfg.vocab)
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(d, profiler_options=opts):
        engine.run(reqs)
    path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    spans, host = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            mine = [e for e in line.events if e.name.startswith("engine.")]
            spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                         for e in mine)
            if mine:  # the engine's thread, named after the executable
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events)
    return reqs, spans, host


def _parent(span, spans):
    """The name of the tightest engine span that holds ``span``."""
    name, s, e, _ = span
    holders = [o for o in spans if o is not span and o[1] <= s and e <= o[2]]
    return min(holders, key=lambda o: o[2] - o[1])[0] if holders else None


def test_every_span_of_the_tree_is_traced(traced):
    _, spans, host = traced
    assert {n for n, *_ in spans} == set(PARENT)
    # the benchmark's reduction, given the engine thread's events, sees
    # each of them as an innermost span
    assert set(PARENT) <= {n for _, _, n in T.innermost(host)}


def test_spans_nest_strictly(traced):
    _, spans, _ = traced
    for a in spans:
        for b in spans:
            disjoint = a[2] <= b[1] or b[2] <= a[1]
            nested = (a[1] <= b[1] and b[2] <= a[2]) or (
                b[1] <= a[1] and a[2] <= b[2])
            assert disjoint or nested, (a, b)
    for span in spans:
        assert _parent(span, spans) == PARENT[span[0]], span


def test_admission_spans_carry_rid(traced):
    reqs, spans, _ = traced
    for name in PER_REQUEST:
        assert sorted(m["rid"] for n, _, _, m in spans if n == name) == [
            r.rid for r in reqs]
    prefill = {m["rid"]: m for n, _, _, m in spans if n == "engine.prefill"}
    assert {rid: m["prompt_len"] for rid, m in prefill.items()} == {
        r.rid: len(r.prompt) for r in reqs}
    assert all(0 <= m["slot"] < SLOTS for m in prefill.values())


def test_tokens_under_profiler_match_reference(model_and_params, traced):
    _, model, params = model_and_params
    reqs, _, _ = traced
    for r in reqs:
        assert r.out == greedy_decode_reference(model, params, r.prompt,
                                                r.max_new, CAP)


def test_phase_counters_cover_run(model_and_params):
    """Over a run of some forty steps, so that one pause of the host between
    two phases does not decide the share."""
    cfg, model, params = model_and_params
    engine = ContinuousEngine(model, params, 4, 32)
    reqs = _requests(cfg.vocab, [(4, 12), (8, 16), (4, 1), (12, 9), (8, 14),
                                 (4, 16), (12, 5), (8, 11)])
    engine.run(copy.deepcopy(reqs))  # compile
    before = engine.stats()
    engine.run(reqs)
    after = engine.stats()
    d = {k: after[k] - before[k] for k in ("run_s",) + PHASES}
    phases = sum(d[k] for k in PHASES)
    assert 0.95 * d["run_s"] <= phases <= d["run_s"], d
    assert all(d[k] > 0 for k in PHASES), d


def test_program_names(model_and_params):
    cfg, model, params = model_and_params
    engine = _engine(model, params)
    batch = {"tokens": jnp.zeros((1, 4), jnp.int32)}
    one, _, _ = jax.eval_shape(engine._prefill, params, batch)
    tok = jnp.zeros(SLOTS, jnp.int32)
    lowered = {
        "jit_prefill": engine._prefill.lower(params, batch),
        "jit_insert": engine._insert.lower(engine.cache, one, 0),
        "jit_decode_step": engine._decode.lower(params, engine.cache, tok, tok),
    }
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text(), name


def test_cache_written_in_place(model_and_params):
    """Every decode step and insert takes the live cache as donated: the
    arrays held before a run are consumed, the engine's own stay live."""
    cfg, model, params = model_and_params
    engine = _engine(model, params)
    held = jax.tree.leaves(engine.cache)
    engine.run(_requests(cfg.vocab))
    stats = engine.stats()
    assert stats["cache_in_place"] == stats["engine_steps"] + stats["prefills"]
    assert stats["engine_steps"] > 0 and stats["prefills"] == len(SPEC)
    assert all(a.is_deleted() for a in held)
    assert not any(a.is_deleted() for a in jax.tree.leaves(engine.cache))
    # one admission and one step: the cache held before them is consumed
    held = jax.tree.leaves(engine.cache)
    engine.run(_requests(cfg.vocab, [(4, 2)]))
    assert all(a.is_deleted() for a in held)
    assert engine.stats()["cache_in_place"] == stats["cache_in_place"] + 2


def test_serve_reports_host_split(model_and_params):
    cfg, model, params = model_and_params
    stats = serve(model, params, _requests(cfg.vocab), SLOTS, CAP)
    for k in ("run_s",) + PHASES:
        assert stats[k] > 0, k
    split = host_split(stats)
    assert split.startswith("per decode step: sync ") and "per admission" in split
    calls = stats["engine_steps"] + stats["prefills"]
    assert split.endswith(f"cache in place in {calls} of {calls} calls")
    # a run with no steps and no admissions has no split
    assert host_split(serve(model, params, [], SLOTS, CAP)) is None
