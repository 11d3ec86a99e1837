"""Continuous-batching engine: per-slot positions, parity, honest accounting."""
import copy
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.launch.engine import (ContinuousEngine, Request,
                                 greedy_decode_reference, latency_summary)
from repro.launch.serve import serve
from repro.models.model import Model

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("yi_6b").reduced()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def mixed_requests(vocab, spec):
    """spec: list of (prompt_len, max_new)."""
    return [Request(i, list(RNG.integers(0, vocab, plen)), mnew)
            for i, (plen, mnew) in enumerate(spec)]


class TestVectorPos:
    def test_decode_step_vector_pos_matches_per_row_calls(self, model_and_params):
        """decode_step with a [B] pos vector == B independent calls, one row
        each at its [1] pos, on the per-row cache slices (logits and cache
        writes)."""
        cfg, model, params = model_and_params
        B, cap = 3, 12
        positions = np.array([2, 7, 0], np.int32)
        toks = jnp.asarray(RNG.integers(0, cfg.vocab, B), jnp.int32)
        # a non-trivial cache: prefill a length-8 batch, then pretend each
        # row sits at its own depth
        batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (B, 8)),
                                       jnp.int32)}
        cache, _, _ = model.prefill(params, batch, cap)

        vec_logits, vec_cache = model.decode_step(
            params, cache, toks, jnp.asarray(positions))

        for i in range(B):
            row_cache = jax.tree.map(lambda a: a[:, i: i + 1], cache)
            lg, nc = model.decode_step(
                params, row_cache, toks[i: i + 1],
                jnp.asarray(positions[i: i + 1]))
            np.testing.assert_allclose(np.asarray(lg[0]),
                                       np.asarray(vec_logits[i]),
                                       atol=1e-5, rtol=1e-5)
            for a, b in zip(jax.tree.leaves(nc),
                            jax.tree.leaves(jax.tree.map(
                                lambda a: a[:, i: i + 1], vec_cache))):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-5, rtol=1e-5)


# a decode step's cache writes, per mixer kind: attention (yi), attention
# beside mamba, mLSTM beside sLSTM, and an encoder-decoder (cross caches)
WRITE_CONFIGS = {
    "yi_6b": lambda: get_config("yi_6b").reduced(),
    "mamba": lambda: dataclasses.replace(
        get_config("jamba_v01_52b").reduced(), moe=None, attn_every=2,
        attn_offset=1, n_layers=4),
    "xlstm": lambda: dataclasses.replace(
        get_config("xlstm_13b").reduced(), slstm_every=2, slstm_offset=1,
        n_layers=4),
    "enc_dec": lambda: get_config("whisper_large_v3").reduced(),
}
WRITE_CAP = 12


def _jitted(model):
    """The same model, its calls jitted."""
    jitted = dataclasses.replace(model)
    jitted.prefill = jax.jit(model.prefill, static_argnums=2)
    jitted.decode_step = jax.jit(model.decode_step)
    jitted.logits = jax.jit(model.logits)
    return jitted


@pytest.fixture(scope="module", params=list(WRITE_CONFIGS))
def write_case(request):
    cfg = WRITE_CONFIGS[request.param]()
    model = Model(cfg)
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(11)
    seqs = rng.integers(0, cfg.vocab, (3, WRITE_CAP))
    frames = (0.1 * rng.standard_normal((3, cfg.n_frontend_tokens,
                                         cfg.d_model))
              if cfg.encoder_decoder else None)
    return cfg, _jitted(model), params, seqs, frames


def _inputs(cfg, seqs, frames, rows, length):
    batch = {"tokens": jnp.asarray(seqs[rows, :length], jnp.int32)}
    if cfg.encoder_decoder:
        batch["frames"] = jnp.asarray(frames[rows], jnp.float32)
    return batch


def _row_cache(model, params, seqs, frames, r, p):
    """Row r's cache holding its first p tokens (cap WRITE_CAP): what the
    engine's insert puts in a slot."""
    if p:
        return model.prefill(params, _inputs(model.cfg, seqs, frames, [r], p),
                             WRITE_CAP)[0]
    cache = model.init_cache(1, WRITE_CAP)
    if not model.cfg.encoder_decoder:
        return cache
    # the encoder's keys and values come from the frames, not the tokens
    enc = model.prefill(params, _inputs(model.cfg, seqs, frames, [r], 1),
                        WRITE_CAP)[0]
    return tuple({**c, "xk": e["xk"], "xv": e["xv"]}
                 for c, e in zip(cache, enc))


def _check_step(cfg, model, params, seqs, frames, cache, pos, live):
    """One decode step at ``pos`` [B] over ``cache``: in ``k``/``v`` it
    writes exactly one position per row and layer and leaves every other
    element bitwise as it was; recurrent states step; cross caches pass
    through; the logits of the ``live`` rows match the full-sequence
    forward."""
    rows_pos = np.asarray(pos, np.int32)
    toks = np.array([seqs[r, p] if r in live else 0
                     for r, p in enumerate(rows_pos)], np.int32)
    old = jax.tree.map(np.asarray, cache)
    logits, new = model.decode_step(params, cache, jnp.asarray(toks),
                                    jnp.asarray(rows_pos))
    full = model.logits(params, _inputs(cfg, seqs, frames, sorted(live),
                                        WRITE_CAP))
    for i, r in enumerate(sorted(live)):
        np.testing.assert_allclose(np.asarray(logits[r]),
                                   np.asarray(full[i, rows_pos[r]]),
                                   atol=1e-5, rtol=1e-5)
    # keys and values at every position, from the whole sequences; a
    # recurrent state from the row's first pos + 1 tokens
    whole = model.prefill(params, _inputs(cfg, seqs, frames, sorted(live),
                                          WRITE_CAP), WRITE_CAP)[0]
    recurrent = any(m != "attention" for m, _ in cfg.pattern())
    refs = {r: _row_cache(model, params, seqs, frames, r, rows_pos[r] + 1)
            for r in live if recurrent}
    for pp, (kinds, o, n) in enumerate(zip(cfg.pattern(), old, new)):
        assert set(n) == set(o)
        for key in ("xk", "xv"):
            if key in o:
                np.testing.assert_array_equal(np.asarray(n[key]), o[key])
        if kinds[0] == "attention":
            for key in ("k", "v"):
                got = np.asarray(n[key])
                keep = np.ones(got.shape, bool)
                for r, p in enumerate(rows_pos):
                    keep[:, r, :, p, :] = False
                    assert not np.array_equal(got[:, r, :, p],
                                              o[key][:, r, :, p]), (key, r)
                assert np.array_equal(got[keep], o[key][keep]), key
                for i, r in enumerate(sorted(live)):
                    p = rows_pos[r]
                    np.testing.assert_allclose(
                        got[:, r, :, p], np.asarray(whole[pp][key])[:, i, :, p],
                        atol=1e-5, rtol=1e-5)
        else:
            for key in set(n) - {"xk", "xv"}:
                for r, ref in refs.items():
                    np.testing.assert_allclose(
                        np.asarray(n[key])[:, r],
                        np.asarray(ref[pp][key])[:, 0], atol=1e-5, rtol=1e-5)


class TestInPlaceWrite:
    """A decode step reads the cache and writes each row's new key and
    value at its own position, and nothing else."""

    def test_per_row_positions(self, write_case):
        """Rows at 0, mid, cap-1, and a free slot as the engine leaves one:
        pos 0, token 0, and what another request left in its cache."""
        cfg, model, params, seqs, frames = write_case
        pos = [0, 5, WRITE_CAP - 1, 0]
        rows = [_row_cache(model, params, seqs, frames, r, p)
                for r, p in enumerate(pos[:3])]
        cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1),
                             *rows, rows[1])
        _check_step(cfg, model, params, seqs, frames, cache, pos,
                    live={0, 1, 2})

    def test_uniform_pos(self, write_case):
        """A batched prefill of equal lengths, then one step with every row
        at the same position."""
        cfg, model, params, seqs, frames = write_case
        cache, pos, _ = model.prefill(
            params, _inputs(cfg, seqs, frames, [0, 1, 2], 5), WRITE_CAP)
        _check_step(cfg, model, params, seqs, frames, cache, [int(pos)] * 3,
                    live={0, 1, 2})


class TestGreedyParity:
    """Acceptance: continuous output is token-for-token identical to
    one-request-at-a-time sequential decode, across mixed prompt lengths and
    mixed max_new, for attention and for the recurrent mixers."""

    SPEC = [(4, 3), (8, 6), (4, 5), (8, 2), (12, 4), (4, 6), (12, 7)]

    @pytest.mark.parametrize("name", ["yi_6b", "mamba", "xlstm"])
    def test_continuous_matches_sequential(self, name):
        cfg = WRITE_CONFIGS[name]()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        base = mixed_requests(cfg.vocab, self.SPEC)
        cap = max(len(r.prompt) + r.max_new for r in base) + 2

        cont = copy.deepcopy(base)
        serve(model, params, cont, slots=3, cap=cap)

        for r in cont:
            assert len(r.out) == r.max_new
        oracle = _jitted(model)
        seq_out = {r.rid: greedy_decode_reference(oracle, params, r.prompt,
                                                  r.max_new, cap)
                   for r in base}
        assert {r.rid: r.out for r in cont} == seq_out


class TestAccounting:
    def test_free_slot_reported_as_waste(self, model_and_params):
        """3 requests on a 4-slot engine: the free slot's decode work lands
        in wasted_slot_steps, never in slot_steps."""
        cfg, model, params = model_and_params
        reqs = mixed_requests(cfg.vocab, [(6, 5)] * 3)
        stats = serve(model, params, reqs, slots=4, cap=16)
        # 4 decode launches (max_new-1), all three requests live throughout
        assert stats["engine_steps"] == 4
        assert stats["slot_steps"] == 4 * 3
        assert stats["wasted_slot_steps"] == 4
        assert (stats["slot_steps"] + stats["wasted_slot_steps"]
                == stats["engine_steps"] * 4)
        assert stats["tokens"] == 15

    def test_freed_slot_refilled_before_it_is_waste(self, model_and_params):
        """A short request's slot is refilled from the queue on the step it
        frees; it counts as waste only once the queue is empty."""
        cfg, model, params = model_and_params
        reqs = mixed_requests(cfg.vocab, [(6, 2), (6, 6), (6, 3)])
        stats = serve(model, params, reqs, slots=2, cap=16)
        # request 0 frees slot 0 after 1 step; request 2 takes it for the
        # next 2; request 1 then drains alone for its last 2 steps
        assert stats["prefills"] == 3
        assert stats["engine_steps"] == 5
        assert stats["slot_steps"] == 1 + 5 + 2
        assert stats["wasted_slot_steps"] == 2
        assert (stats["slot_steps"] + stats["wasted_slot_steps"]
                == stats["engine_steps"] * 2)
        assert all(r.t_first is not None and r.t_done is not None
                   for r in reqs)
        assert reqs[2].t_first >= reqs[0].t_done

    def test_tokens_and_latency_report(self, model_and_params):
        """A mixed workload: every request gets its max_new tokens, and the
        latency report has its shape."""
        cfg, model, params = model_and_params
        spec = [(4, 2), (4, 8), (8, 3), (8, 8), (4, 5), (8, 2)]
        reqs = mixed_requests(cfg.vocab, spec)
        cap = max(len(r.prompt) + r.max_new for r in reqs) + 2
        stats = serve(model, params, reqs, slots=2, cap=cap)
        assert stats["tokens"] == sum(m for _, m in spec)
        assert [len(r.out) for r in reqs] == [m for _, m in spec]
        for key in ("ttft_s", "latency_s"):
            assert set(stats[key]) == {"p50", "p95", "p99", "mean"}
        assert len(stats["requests"]) == len(spec)


class TestSlotLifecycle:
    def test_eos_frees_slot_early(self, model_and_params):
        """A request that emits its eos_id stops there; the freed slot is
        refilled and the remaining queue still drains correctly."""
        cfg, model, params = model_and_params
        base = mixed_requests(cfg.vocab, [(6, 6), (6, 6), (6, 6)])
        cap = 16
        ref = greedy_decode_reference(model, params, base[0].prompt, 6, cap)
        eos = ref[2]  # cut request 0 at its third emitted token
        reqs = copy.deepcopy(base)
        reqs[0].eos_id = eos
        stats = serve(model, params, reqs, slots=2, cap=cap)
        assert reqs[0].out[-1] == eos
        assert len(reqs[0].out) <= 3
        assert reqs[0].out == ref[: len(reqs[0].out)]
        for r in reqs[1:]:
            assert len(r.out) == 6
        assert stats["prefills"] == 3

    def test_deadline_truncates_and_is_counted(self, model_and_params):
        """deadline_s=0 is already past at admission: the request still
        gets its first (prefill) token, then frees the slot."""
        cfg, model, params = model_and_params
        reqs = mixed_requests(cfg.vocab, [(6, 50), (6, 4)])
        reqs[0].deadline_s = 0.0
        stats = serve(model, params, reqs, slots=1, cap=64)
        assert reqs[0].truncated and len(reqs[0].out) == 1
        assert reqs[0].t_done is not None
        assert len(reqs[1].out) == 4 and not reqs[1].truncated
        assert stats["deadline_truncations"] == 1


def test_latency_summary_percentiles():
    s = latency_summary([0.1] * 99 + [1.0])
    assert s["p50"] == pytest.approx(0.1)
    assert s["p99"] >= 0.1 and s["p99"] <= 1.0
    assert latency_summary([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                                   "mean": 0.0}
