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
from repro.launch.serve import group_into_waves, serve
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
    def test_decode_step_vector_pos_matches_scalar_calls(self, model_and_params):
        """decode_step with a [B] pos vector == B independent scalar-pos
        calls on the per-row cache slices (logits and cache writes)."""
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
                jnp.asarray(positions[i], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg[0]),
                                       np.asarray(vec_logits[i]),
                                       atol=1e-5, rtol=1e-5)
            for a, b in zip(jax.tree.leaves(nc),
                            jax.tree.leaves(jax.tree.map(
                                lambda a: a[:, i: i + 1], vec_cache))):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-5, rtol=1e-5)

    def test_scalar_pos_path_unchanged(self, model_and_params):
        """Scalar pos must still take the lockstep path (wave fallback)."""
        cfg, model, params = model_and_params
        batch = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (2, 8)),
                                       jnp.int32)}
        cache, pos, _ = model.prefill(params, batch, 12)
        toks = jnp.asarray(RNG.integers(0, cfg.vocab, 2), jnp.int32)
        lg_s, _ = model.decode_step(params, cache, toks, pos)
        lg_v, _ = model.decode_step(
            params, cache, toks, jnp.full((2,), int(pos), jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_v),
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
    jitted = dataclasses.replace(model)  # the same model, its calls jitted
    jitted.prefill = jax.jit(model.prefill, static_argnums=2)
    jitted.decode_step = jax.jit(model.decode_step)
    jitted.logits = jax.jit(model.logits)
    return cfg, jitted, params, seqs, frames


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
    """One decode step at ``pos`` over ``cache``: in ``k``/``v`` it writes
    exactly one position per row and layer and leaves every other element
    bitwise as it was; recurrent states step; cross caches pass through;
    the logits of the ``live`` rows match the full-sequence forward."""
    pos = np.asarray(pos, np.int32)
    rows_pos = np.broadcast_to(pos, jax.tree.leaves(cache)[0].shape[1:2])
    toks = np.array([seqs[r, p] if r in live else 0
                     for r, p in enumerate(rows_pos)], np.int32)
    old = jax.tree.map(np.asarray, cache)
    logits, new = model.decode_step(params, cache, jnp.asarray(toks),
                                    jnp.asarray(pos))
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

    def test_scalar_pos(self, write_case):
        """The wave path: every row at one position."""
        cfg, model, params, seqs, frames = write_case
        cache, pos, _ = model.prefill(
            params, _inputs(cfg, seqs, frames, [0, 1, 2], 5), WRITE_CAP)
        _check_step(cfg, model, params, seqs, frames, cache, int(pos),
                    live={0, 1, 2})


class TestGreedyParity:
    """Acceptance: continuous output is token-for-token identical to the
    wave scheduler and to one-request-at-a-time sequential decode, across
    mixed prompt lengths and mixed max_new."""

    SPEC = [(4, 3), (8, 6), (4, 5), (8, 2), (12, 4), (4, 6), (12, 7)]

    def test_continuous_matches_wave_and_sequential(self, model_and_params):
        cfg, model, params = model_and_params
        base = mixed_requests(cfg.vocab, self.SPEC)
        cap = max(len(r.prompt) + r.max_new for r in base) + 2

        cont = copy.deepcopy(base)
        serve(model, params, cont, slots=3, cap=cap, scheduler="continuous")
        wave = copy.deepcopy(base)
        serve(model, params, wave, slots=3, cap=cap, scheduler="wave")

        for r in cont:
            assert len(r.out) == r.max_new
        cont_out = {r.rid: r.out for r in cont}
        assert cont_out == {r.rid: r.out for r in wave}
        seq_out = {r.rid: greedy_decode_reference(model, params, r.prompt,
                                                  r.max_new, cap)
                   for r in base}
        assert cont_out == seq_out


class TestAccounting:
    def test_wave_pad_slots_reported_as_waste(self, model_and_params):
        """A 3-request wave on a 4-slot engine: the pad row's decode work
        must land in wasted_slot_steps, never in slot_steps."""
        cfg, model, params = model_and_params
        reqs = mixed_requests(cfg.vocab, [(6, 5)] * 3)
        stats = serve(model, params, reqs, slots=4, cap=16, scheduler="wave")
        # 4 decode launches (max_new-1) x 1 pad slot; all requests live
        # the whole wave, so no finished-slot waste on top
        assert stats["engine_steps"] == 4
        assert stats["slot_steps"] == 4 * 3
        assert stats["wasted_slot_steps"] == 4
        assert stats["tokens"] == 15

    def test_wave_finished_slots_reported_as_waste(self, model_and_params):
        """Mixed max_new in one wave: the short request's idle tail counts
        as waste while the long one drains."""
        cfg, model, params = model_and_params
        reqs = mixed_requests(cfg.vocab, [(6, 2), (6, 6)])
        stats = serve(model, params, reqs, slots=2, cap=16, scheduler="wave")
        # 5 decode launches; request 0 is live for 1 of them
        assert stats["engine_steps"] == 5
        assert stats["slot_steps"] == 5 + 1
        assert stats["wasted_slot_steps"] == 4
        assert all(r.t_first is not None and r.t_done is not None
                   for r in reqs)

    def test_continuous_beats_wave_on_waste(self, model_and_params):
        """The acceptance inequality on a mixed workload: strictly fewer
        wasted slot-steps, same tokens."""
        cfg, model, params = model_and_params
        spec = [(4, 2), (4, 8), (8, 3), (8, 8), (4, 5), (8, 2)]
        base = mixed_requests(cfg.vocab, spec)
        cap = max(len(r.prompt) + r.max_new for r in base) + 2
        wave = copy.deepcopy(base)
        sw = serve(model, params, wave, slots=2, cap=cap, scheduler="wave")
        cont = copy.deepcopy(base)
        sc = serve(model, params, cont, slots=2, cap=cap,
                   scheduler="continuous")
        assert sc["tokens"] == sw["tokens"] == sum(m for _, m in spec)
        assert sc["wasted_slot_steps"] < sw["wasted_slot_steps"]
        # latency report shape
        for s in (sw, sc):
            for key in ("ttft_s", "latency_s"):
                assert set(s[key]) == {"p50", "p95", "p99", "mean"}
            assert len(s["requests"]) == len(spec)

    def test_group_into_waves_buckets_by_length(self, model_and_params):
        cfg, _, _ = model_and_params
        reqs = mixed_requests(cfg.vocab, [(4, 1), (8, 1), (4, 1), (4, 1)])
        waves = group_into_waves(reqs, slots=2)
        assert [[r.rid for r in w] for w in waves] == [[0, 2], [3], [1]]


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
        stats = serve(model, params, reqs, slots=2, cap=cap,
                      scheduler="continuous")
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
        stats = serve(model, params, reqs, slots=1, cap=64,
                      scheduler="continuous")
        assert reqs[0].truncated and len(reqs[0].out) == 1
        assert reqs[0].t_done is not None
        assert len(reqs[1].out) == 4 and not reqs[1].truncated
        assert stats["deadline_truncations"] == 1

    def test_refresh_polled_at_admission_boundary(self, model_and_params):
        """The snapshot poll rides admissions, not the first batch."""
        cfg, model, params = model_and_params
        calls = []

        def refresh():
            calls.append(True)
            return len(calls) == 1

        reqs = mixed_requests(cfg.vocab, [(6, 3)] * 4)
        stats = serve(model, params, reqs, slots=2, cap=16,
                      scheduler="continuous", refresh=refresh)
        assert calls  # polled for the second admission batch
        assert stats["cache_reloads"] == 1


def test_latency_summary_percentiles():
    s = latency_summary([0.1] * 99 + [1.0])
    assert s["p50"] == pytest.approx(0.1)
    assert s["p99"] >= 0.1 and s["p99"] <= 1.0
    assert latency_summary([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                                   "mean": 0.0}
