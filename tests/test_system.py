"""End-to-end behaviour: the public API flows a user would actually run."""
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.launch.serve import Request, serve
from repro.launch.train import TrainOptions, train
from repro.models.model import Model


class TestTrainEndToEnd:
    def test_loss_decreases_on_learnable_data(self):
        """Train on a fixed repeating sequence — CE must fall well below the
        ln(V) random floor within ~60 steps."""
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        from repro.optim import adamw

        opt_cfg = adamw.AdamWConfig(lr=3e-3, weight_decay=0.0)
        params = model.init(jax.random.key(0))
        state = adamw.init_state(opt_cfg, params)
        base = jnp.arange(33, dtype=jnp.int32) % cfg.vocab
        toks = jnp.tile(base[None], (4, 1))
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

        from repro.launch.steps import make_train_step

        step = jax.jit(make_train_step(model, opt_cfg))
        first = None
        for _ in range(60):
            params, state, metrics = step(params, state, batch)
            if first is None:
                first = float(metrics["ce"])
        last = float(metrics["ce"])
        assert last < first * 0.5
        assert last < 2.0  # far below ln(256) = 5.55

    def test_grad_accum_equivalent_to_large_batch(self):
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        from repro.launch.steps import make_train_step
        from repro.optim import adamw

        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                  jnp.int32),
        }
        params = model.init(jax.random.key(0))
        state = adamw.init_state(opt_cfg, params)
        p1, _, m1 = jax.jit(make_train_step(model, opt_cfg))(params, state,
                                                             batch)
        p4, _, m4 = jax.jit(make_train_step(model, opt_cfg, accum_steps=4))(
            params, state, batch)
        assert float(m1["ce"]) == pytest.approx(float(m4["ce"]), rel=1e-4)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=2e-5)

    def test_int8_grad_compression_trains(self):
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        from repro.launch.steps import make_train_step
        from repro.optim import adamw

        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        params = model.init(jax.random.key(0))
        state = adamw.init_state(opt_cfg, params)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                  jnp.int32),
        }
        step = jax.jit(make_train_step(model, opt_cfg,
                                       grad_compression="int8"))
        params, state, metrics = step(params, state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestServeEndToEnd:
    def test_batched_serving(self):
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        reqs = [Request(i, list(rng.integers(0, cfg.vocab, 8)), 6)
                for i in range(4)]
        stats = serve(model, params, reqs, slots=2, cap=16)
        assert all(len(r.out) == 6 for r in reqs)
        assert stats["tokens"] == 24

    def test_greedy_decode_matches_argmax_forward(self):
        """The engine's first generated token == argmax of the prefill
        logits' last position computed by the parallel forward."""
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(1)
        prompt = list(rng.integers(0, cfg.vocab, 8))
        reqs = [Request(0, prompt, 2)]
        serve(model, params, reqs, slots=1, cap=12)
        batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
        logits = model.logits(params, {**batch, "labels": batch["tokens"]})
        assert reqs[0].out[0] == int(jnp.argmax(logits[0, -1]))


class TestElastic:
    def test_checkpoint_reshards_across_device_counts(self, tmp_path):
        """Save params from a 1-device run, restore onto a 4-device mesh in a
        child interpreter (elastic shrink/grow path)."""
        cfg = get_config("yi_6b").reduced()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        from repro.checkpoint import store

        store.save(str(tmp_path), 3, params)

        code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs.base import get_config
from repro.checkpoint.elastic import restore_on_mesh
from repro.launch.mesh import make_mesh
from repro.models.model import Model

cfg = get_config("yi_6b").reduced()
model = Model(cfg)
like = jax.eval_shape(lambda: model.init(jax.random.key(0)))
mesh = make_mesh((2, 2), ("data", "model"))
tree, manifest = restore_on_mesh(r"{tmp_path}", like, mesh, kind="params")
leaf = jax.tree.leaves(tree)[0]
assert manifest["step"] == 3
assert len(leaf.sharding.device_set) >= 1
total = sum(x.size for x in jax.tree.leaves(tree))
print("ELASTIC_OK", total)
"""
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/tmp",
                 "JAX_PLATFORMS": "cpu"},
            timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "ELASTIC_OK" in proc.stdout
