"""MoE routing correctness vs brute force; Mamba/xLSTM vs step oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod

RNG = np.random.default_rng(3)


def _loop(cfg, p, x, idx, gates):
    """The routed experts' output, one (token, expert) pair at a time."""
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for s in range(idx.shape[1]):
            e = int(idx[t, s])
            act = jax.nn.silu(x[t] @ p["w1"][e]) * (x[t] @ p["w3"][e])
            want[t] += float(gates[t, s]) * np.asarray(act @ p["w2"][e])
    return want


class TestMoE:
    def _cfg(self):
        return get_config("qwen3_moe_235b_a22b").reduced()

    def test_matches_bruteforce_when_capacity_ample(self):
        """The dropless layer (no capacity: every pair computed) == an
        explicit per-token loop."""
        cfg = self._cfg()
        p = moe_mod.init_moe(cfg, jax.random.key(0))
        x = jnp.asarray(RNG.standard_normal((2, 8, cfg.d_model)), jnp.float32)
        y, aux, _ = moe_mod.apply_moe(cfg, p, x)

        idx, gates, _ = moe_mod.route(cfg, p, x)
        flat = lambda a: a.reshape((16,) + a.shape[2:])
        want = _loop(cfg, p, flat(x), flat(idx), flat(gates))
        np.testing.assert_allclose(np.asarray(flat(y)), want, atol=1e-4,
                                   rtol=1e-3)
        assert np.isfinite(float(aux))

    def test_skewed_router_drops_nothing(self):
        """A router that sends every token to the same experts: nothing is
        dropped (the capacity path dropped all but a few), and each pair
        still equals the per-token loop."""
        cfg = self._cfg()
        p = moe_mod.init_moe(cfg, jax.random.key(0))
        p = dict(p, router=jnp.zeros_like(p["router"]).at[:, :2].set(
            jnp.asarray([1.0, 0.5])))  # logits ~ x.sum(): same two experts
        x = jnp.asarray(np.abs(RNG.standard_normal((1, 32, cfg.d_model))),
                        jnp.float32)
        y, _, stats = moe_mod.apply_moe(cfg, p, x)
        idx, gates, _ = moe_mod.route(cfg, p, x)
        assert set(np.asarray(idx).ravel().tolist()) == {0, 1}
        want = _loop(cfg, p, x[0], idx[0], gates[0])
        np.testing.assert_allclose(np.asarray(y[0]), want, atol=1e-4, rtol=1e-3)
        assert np.asarray(stats).tolist() == [64, 2, 32]

    def test_aux_loss_balanced_router_is_minimal(self):
        """Uniform routing gives aux ~ 1 (the Switch loss optimum)."""
        cfg = self._cfg()
        p = moe_mod.init_moe(cfg, jax.random.key(0))
        p = dict(p, router=jnp.zeros_like(p["router"]))  # uniform probs
        x = jnp.asarray(RNG.standard_normal((2, 64, cfg.d_model)), jnp.float32)
        _, _, aux = moe_mod.route(cfg, p, x)
        assert float(aux) == pytest.approx(1.0, abs=0.25)


class TestMambaOracle:
    def test_chunked_scan_matches_stepwise(self):
        cfg = get_config("jamba_v01_52b").reduced()
        p = ssm_mod.init_mamba(cfg, jax.random.key(0))
        b, s = 2, 24
        x = jnp.asarray(0.5 * RNG.standard_normal((b, s, cfg.d_model)),
                        jnp.float32)
        y_par, state = ssm_mod.mamba_forward(cfg, p, x, chunk=8,
                                             return_state=True)

        cache = ssm_mod.init_mamba_cache(cfg, b, jnp.float32)
        ys = []
        for t in range(s):
            yt, cache = ssm_mod.mamba_decode(cfg, p, x[:, t: t + 1], cache)
            ys.append(yt)
        y_seq = jnp.concatenate(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                                   atol=2e-4, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(state["h"]),
                                   np.asarray(cache["h"]), atol=2e-4,
                                   rtol=2e-3)

    def test_chunk_size_invariance(self):
        cfg = get_config("jamba_v01_52b").reduced()
        p = ssm_mod.init_mamba(cfg, jax.random.key(0))
        x = jnp.asarray(0.5 * RNG.standard_normal((1, 32, cfg.d_model)),
                        jnp.float32)
        y8 = ssm_mod.mamba_forward(cfg, p, x, chunk=8)
        y32 = ssm_mod.mamba_forward(cfg, p, x, chunk=32)
        np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), atol=1e-5,
                                   rtol=1e-5)


class TestXlstmOracle:
    def test_mlstm_chunkwise_matches_stepwise(self):
        cfg = get_config("xlstm_13b").reduced()
        p = xlstm_mod.init_mlstm(cfg, jax.random.key(0))
        b, s = 2, 24
        x = jnp.asarray(0.5 * RNG.standard_normal((b, s, cfg.d_model)),
                        jnp.float32)
        y_par, state = xlstm_mod.mlstm_forward(cfg, p, x, return_state=True)

        cache = xlstm_mod.init_mlstm_cache(cfg, b)
        ys = []
        for t in range(s):
            yt, cache = xlstm_mod.mlstm_decode(cfg, p, x[:, t: t + 1], cache)
            ys.append(yt)
        y_seq = jnp.concatenate(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                                   atol=5e-4, rtol=5e-3)
        np.testing.assert_allclose(np.asarray(state["C"]),
                                   np.asarray(cache["C"]), atol=5e-4,
                                   rtol=5e-3)

    def test_slstm_forward_matches_decode(self):
        cfg = get_config("xlstm_13b").reduced()
        p = xlstm_mod.init_slstm(cfg, jax.random.key(0))
        b, s = 2, 16
        x = jnp.asarray(0.5 * RNG.standard_normal((b, s, cfg.d_model)),
                        jnp.float32)
        y_fwd, state = xlstm_mod.slstm_forward(cfg, p, x, return_state=True)
        cache = xlstm_mod.init_slstm_cache(cfg, b)
        ys = []
        for t in range(s):
            yt, cache = xlstm_mod.slstm_decode(cfg, p, x[:, t: t + 1], cache)
            ys.append(yt)
        np.testing.assert_allclose(np.asarray(y_fwd),
                                   np.asarray(jnp.concatenate(ys, axis=1)),
                                   atol=1e-5, rtol=1e-5)

    def test_mlstm_forget_gate_decay(self):
        """With a strongly negative forget gate (and the exp input gate
        neutralised — otherwise a large input legitimately dominates the
        matrix memory), early-token perturbations must decay away."""
        cfg = get_config("xlstm_13b").reduced()
        p = xlstm_mod.init_mlstm(cfg, jax.random.key(0))
        p = dict(p, f_bias=jnp.full_like(p["f_bias"], -8.0),
                 w_i=jnp.zeros_like(p["w_i"]))
        x = jnp.asarray(RNG.standard_normal((1, 32, cfg.d_model)), jnp.float32)
        x2 = x.at[:, :8].set(x[:, :8] + 1.0)  # perturb early tokens only
        y1 = xlstm_mod.mlstm_forward(cfg, p, x)
        y2 = xlstm_mod.mlstm_forward(cfg, p, x2)
        late1, late2 = np.asarray(y1[:, -1]), np.asarray(y2[:, -1])
        assert np.abs(late1 - late2).max() < 1e-2
