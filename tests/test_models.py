"""Per-arch smoke tests (reduced configs) + decode/prefill consistency."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models.model import Model

RNG = np.random.default_rng(0)
B, S = 2, 16


def make_batch(cfg, b=B, s=S):
    batch = {
        "tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(RNG.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.frontend == "audio":
        batch["frames"] = jnp.asarray(
            0.1 * RNG.standard_normal((b, cfg.n_frontend_tokens, cfg.d_model)),
            jnp.float32)
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(
            0.1 * RNG.standard_normal((b, cfg.n_frontend_tokens, cfg.d_model)),
            jnp.float32)
    return batch


# big hybrid archs (deep scans / many experts) dominate suite wall time;
# their cells run as slow so tier-1 stays well under its 120 s budget
HEAVY_ARCHS = {"jamba_v01_52b", "xlstm_13b"}


def _arch_param(arch, heavy=HEAVY_ARCHS):
    marks = [pytest.mark.slow] if arch in heavy else []
    return pytest.param(arch, marks=marks)


@pytest.mark.parametrize("arch", [_arch_param(a) for a in ARCH_IDS])
class TestArchSmoke:
    def test_forward_and_train_step(self, arch):
        cfg = get_config(arch).reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg)
        loss, metrics = jax.jit(model.loss)(params, batch)
        assert jnp.isfinite(loss), arch
        assert loss.shape == ()
        # one SGD-ish step moves the loss (gradients flow end to end)
        grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
        gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                    for g in jax.tree.leaves(grads))
        assert np.isfinite(gnorm) and gnorm > 0, arch

    def test_logit_shapes(self, arch):
        cfg = get_config(arch).reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        logits = model.logits(params, make_batch(cfg))
        assert logits.shape == (B, S, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


DECODE_TOL = {
    "jamba_v01_52b": 5e-4, "xlstm_13b": 5e-3,
}


@pytest.mark.parametrize("arch", [
    _arch_param(a, heavy=HEAVY_ARCHS | {"qwen3_moe_235b_a22b"})
    for a in ("yi_6b", "qwen3_moe_235b_a22b", "jamba_v01_52b", "xlstm_13b",
              "whisper_large_v3", "internvl2_1b")
])
class TestDecodeConsistency:
    """Teacher-forced decode (step-by-step with caches) must match the full
    parallel forward — validates KV caches, SSM states and positions."""

    def test_prefill_plus_decode_matches_forward(self, arch):
        cfg = get_config(arch).reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(1))
        batch = make_batch(cfg)
        full = model.logits(params, batch)  # [B,S,V]

        npfx = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
        cap = S + npfx
        prefix = {**batch, "tokens": batch["tokens"][:, : S - 1]}
        cache, pos, _ = model.prefill(params, prefix, cap)
        lg, _ = model.decode_step(params, cache, batch["tokens"][:, S - 1],
                                  jnp.full((B,), pos, jnp.int32))
        tol = DECODE_TOL.get(arch, 2e-4)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, -1]),
                                   atol=tol * 50, rtol=tol * 10)

    def test_decode_from_scratch_matches_forward(self, arch):
        cfg = get_config(arch).reduced()
        if cfg.encoder_decoder or cfg.frontend == "vision":
            pytest.skip("prefix modalities covered by the prefill test")
        model = Model(cfg)
        params = model.init(jax.random.key(1))
        batch = make_batch(cfg)
        full = model.logits(params, batch)
        cache = model.init_cache(B, S)
        outs = []
        for t in range(S):
            lg, cache = model.decode_step(params, cache, batch["tokens"][:, t],
                                          jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        dec = jnp.stack(outs, axis=1)
        tol = DECODE_TOL.get(arch, 2e-4)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                                   atol=tol * 50, rtol=tol * 10)


class TestPatternAssembly:
    def test_jamba_pattern(self):
        cfg = get_config("jamba_v01_52b")
        pat = cfg.pattern()
        assert len(pat) == 8
        assert sum(1 for m, _ in pat if m == "attention") == 1
        assert sum(1 for m, _ in pat if m == "mamba") == 7
        assert sum(1 for _, m in pat if m == "moe") == 4  # every 2nd layer

    def test_xlstm_pattern(self):
        cfg = get_config("xlstm_13b")
        pat = cfg.pattern()
        assert sum(1 for m, _ in pat if m == "slstm") == 1
        assert sum(1 for m, _ in pat if m == "mlstm") == 7
        assert all(mlp == "none" for _, mlp in pat)

    def test_param_count_formula_close_to_eval_shape(self):
        """The analytic 6·N·D bookkeeping must track the real tree size."""
        for arch in ("yi_6b", "qwen3_moe_235b_a22b", "whisper_large_v3"):
            cfg = get_config(arch)
            model = Model(cfg)
            shapes = jax.eval_shape(lambda m=model: m.init(jax.random.key(0)))
            n_real = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
            n_formula = cfg.param_count()
            assert abs(n_real - n_formula) / n_real < 0.05, (
                arch, n_real, n_formula)
