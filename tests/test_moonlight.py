"""The DeepSeek-V3 block (Moonlight-16B-A3B) at a reduced size of the same
structure: one dense layer and two MoE layers, 8 experts top-2 and one
shared, latent attention at latent 32 and rope 16, float32. Against the
plain reference (``v3_reference.py``): the full forward pass, prefill then
decode through the latent cache, the dropless expert layer and the V3
router; the grouped-matmul kernel in interpret mode; the engine's MoE
counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import v3_reference as ref
from repro.configs.base import MLASpec, get_config
from repro.kernels import moe_gmm
from repro.launch.engine import ContinuousEngine, Request, greedy_decode_reference
from repro.models import moe as moe_mod
from repro.models.model import Model

# float32 on both sides; the program sums in another order (latent-form
# decode, grouped experts, online softmax): agreement to 1e-4
TOL = dict(rtol=1e-4, atol=1e-4)


def small_cfg():
    cfg = get_config("moonlight-16b-a3b").reduced()
    return dataclasses.replace(
        cfg, n_layers=3,
        moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2, n_shared=1),
        mla=MLASpec(kv_rank=32, nope_dim=16, rope_dim=16, v_dim=16))


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    # a selection bias that moves choices (the init's is zero)
    bias = 0.2 * jax.random.normal(jax.random.key(1), (2, cfg.moe.n_experts))
    params["layers"][1]["mlp"]["router_bias"] = bias
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (2, 18)),
                         jnp.int32)
    return cfg, model, params, tokens


def test_structure(setup):
    cfg, _, params, _ = setup
    assert cfg.lead_pattern() == (("mla", "dense"),)
    assert cfg.pattern() == (("mla", "moe"),)
    lead, main = params["layers"]
    assert lead["mlp"]["w1"].shape == (1, cfg.d_model, cfg.d_ff)
    assert main["mlp"]["w1"].shape == (2, 8, cfg.d_model, cfg.moe.d_expert)
    assert main["mlp"]["shared"]["w1"].shape == (2, cfg.d_model, cfg.moe.d_expert)


def test_forward_matches_reference(setup):
    cfg, model, params, tokens = setup
    np.testing.assert_allclose(model.logits(params, {"tokens": tokens}),
                               ref.logits(cfg, params, tokens), **TOL)


def test_prefill_then_latent_decode_matches_reference(setup):
    """Two rows prefilled alone (9 and 13 tokens), put side by side, then
    four steps at per-slot positions through the latent cache: each step's
    logits are the reference's full forward at that position."""
    cfg, model, params, tokens = setup
    want = ref.logits(cfg, params, tokens)
    cap = 20
    lens = [9, 13]
    rows = [model.prefill(params, {"tokens": tokens[r: r + 1, :n]}, cap)[0]
            for r, n in enumerate(lens)]
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *rows)
    # the cache holds only the latent and the rope key, per position
    assert [c["ckv"].shape for c in cache] == [
        (1, 2, cap, 32 + 16), (2, 2, cap, 32 + 16)]
    pos = np.asarray(lens, np.int32)
    for _ in range(4):
        tok = tokens[jnp.arange(2), pos]
        logits, cache = model.decode_step(params, cache, tok, jnp.asarray(pos))
        for r in range(2):
            np.testing.assert_allclose(logits[r], want[r, pos[r]], **TOL)
        pos = pos + 1


def _loop(cfg, p, x, idx, gates):
    """The routed experts' output, one (token, expert) pair at a time."""
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            act = jax.nn.silu(x[t] @ p["w1"][e]) * (x[t] @ p["w3"][e])
            want[t] += float(gates[t, j]) * np.asarray(act @ p["w2"][e])
    return want


@pytest.mark.parametrize("skew", [False, True])
def test_dropless_equals_per_token_loop(setup, skew):
    """Every pair is computed: the layer equals a per-token loop, also when
    a bias sends every token to the same two experts."""
    cfg, _, params, _ = setup
    p = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    if skew:
        p["router_bias"] = jnp.zeros(8).at[3].set(10.0).at[5].set(10.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((24, cfg.d_model)),
                    jnp.float32)
    idx, gates, _ = moe_mod.route(cfg, p, x)
    if skew:
        assert set(np.asarray(idx).ravel().tolist()) == {3, 5}
    got = moe_mod.experts(cfg, p, x, idx, gates)
    np.testing.assert_allclose(got, _loop(cfg, p, x, idx, gates), **TOL)
    y, _, stats = moe_mod.apply_moe(cfg, p, x[None])
    np.testing.assert_allclose(y[0], got + ref.swiglu(p["shared"], x), **TOL)
    pairs, touched, busiest = (int(v) for v in stats)
    assert pairs == 24 * 2 and 2 <= touched <= 8 and 6 <= busiest <= 24
    if skew:
        assert (touched, busiest) == (2, 24)


def test_router_v3(setup):
    """Sigmoid scores; the bias picks and is not in the gates; the picked
    scores renormalised to 1, then scaled by routed_scale."""
    cfg, _, params, _ = setup
    p = jax.tree.map(lambda a: a[0], params["layers"][1]["mlp"])
    x = jnp.asarray(np.random.default_rng(2).standard_normal((16, cfg.d_model)),
                    jnp.float32)
    p["router_bias"] = jnp.zeros(8).at[0].set(5.0)  # expert 0 always picked
    idx, gates, _ = moe_mod.route(cfg, p, x)
    scores = jax.nn.sigmoid(x @ p["router"])
    assert bool(jnp.all(idx[:, 0] == 0))
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True) * cfg.moe.routed_scale,
        rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), cfg.moe.routed_scale, rtol=1e-6)
    # the reference's dense gates agree
    dense = jnp.zeros((16, 8)).at[jnp.arange(16)[:, None], idx].set(gates)
    np.testing.assert_allclose(ref.gates(cfg, p, x), dense, rtol=1e-6)


@pytest.mark.parametrize("experts", [[0] * 40, [2, 5, 5, 1, 5, 2, 7] * 5])
def test_moe_gmm_kernel(experts):
    """The Pallas kernel (interpret mode) on a stack of two layers' weights,
    at the second layer, equals each row times its expert's weights:
    empty experts, one expert for every row, groups across tiles."""
    rng = np.random.default_rng(3)
    e, k, n = 8, 256, 384
    flat = jnp.asarray(experts, jnp.int32)
    pl_ = moe_mod.plan(flat, e)
    x = jnp.asarray(rng.standard_normal((len(experts), k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, e, k, n)), jnp.float32)
    xs = jnp.zeros((pl_.rows, k)).at[pl_.row].set(x)
    want = jnp.einsum("pk,pkn->pn", x, w[1][flat])
    got = moe_gmm.gmm_pallas(xs, w, pl_.tile_expert, pl_.active, pl_.tm,
                             layer=1, interpret=True)
    np.testing.assert_allclose(got[pl_.row], want, rtol=1e-5, atol=1e-4)
    ref_ = moe_gmm.gmm_reference(xs, w, pl_.tile_expert, pl_.tm, layer=1)
    np.testing.assert_allclose(ref_[pl_.row], want, rtol=1e-5, atol=1e-4)
    # padding rows come out zero; each tile holds one expert's rows
    pad = np.setdiff1d(np.arange(pl_.rows), np.asarray(pl_.row))
    assert not np.asarray(got)[pad].any()
    assert pl_.rows - len(experts) < e * pl_.tm


def test_engine_serves_and_counts(setup):
    """The continuous engine serves the block token-for-token as the
    one-request oracle, and its counters count the live rows' routing."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(4)
    reqs = [Request(i, rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate([(5, 6), (9, 3), (4, 8)])]
    engine = ContinuousEngine(model, params, slots=2, cap=20)
    engine.run(reqs)
    for r in reqs:
        assert r.out == greedy_decode_reference(model, params, r.prompt,
                                                r.max_new, 20)
    s = engine.stats()
    moe_layers, k = 2, cfg.moe.top_k
    assert s["moe_pairs"] == s["slot_steps"] * k * moe_layers
    assert s["engine_steps"] * moe_layers <= s["moe_experts_touched"] \
        <= s["engine_steps"] * moe_layers * min(8, 2 * k)
    assert s["engine_steps"] * moe_layers <= s["moe_max_load"] \
        <= s["engine_steps"] * moe_layers * 2


def test_engine_counts_latent_rows(setup):
    """Each decode launch adds, for one layer, the rows the latent kernel
    reads (each slot's blocks below its position, none for a free slot)
    and the rows of the whole cache."""
    from repro.launch.engine import latent_block

    cfg, model, params, _ = setup
    cap, slots = 20, 3
    blk = latent_block(model, cap)
    assert blk == 4  # f32 rows of 48: the largest power of two dividing 20
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, cfg.vocab, n).tolist(), m)
            for i, (n, m) in enumerate([(5, 6), (9, 3), (4, 8), (7, 2)])]
    engine = ContinuousEngine(model, params, slots=slots, cap=cap)
    launched = []
    decode = engine._decode

    def record(params, state, tok, pos):
        launched.append(np.array(pos))  # a copy: it may share the engine's buffer
        return decode(params, state, tok, pos)

    engine._decode = record
    engine.run(reqs)
    s = engine.stats()
    assert len(launched) == s["engine_steps"] > 0
    assert any((p == 0).any() for p in launched)  # some launch had a free slot
    assert s["latent_rows_read"] == sum(int((-(-p // blk)).sum()) * blk
                                        for p in launched)
    assert s["latent_rows_cap"] == len(launched) * slots * cap
    assert sum(int(p.sum()) for p in launched) <= s["latent_rows_read"] \
        < s["latent_rows_cap"]


def test_dense_model_carries_no_counters():
    cfg = get_config("yi-6b").reduced()
    model = Model(cfg)
    engine = ContinuousEngine(model, model.init(jax.random.key(0)), 2, 16)
    engine.run([Request(0, [1, 2, 3], 3)])
    assert jax.tree.structure(engine.cache) == jax.tree.structure(
        model.init_cache(2, 16))
    s = engine.stats()
    assert (s["moe_pairs"], s["moe_experts_touched"], s["moe_max_load"]) == (0, 0, 0)
    assert not {"latent_rows_read", "latent_rows_cap"} & set(s)
