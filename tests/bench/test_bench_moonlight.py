"""The DeepSeek-V3-block cell (moonlight-16b-a3b-9l): found from files
alone, its weights rule, its work counts by hand, its reference against the
program, and a whole run of a small cell of the same block on the CPU."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry, run
from bench.common import arch_config
from bench.deepseek_v3 import flops as F
from bench.deepseek_v3 import reference as ref
from bench.deepseek_v3 import weights as W

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL = "moonlight-16b-a3b-9l.decode_heavy_64"
SEED = 2**31 + 5
# the small cell's limits, from its readings on the CPU: the program reads
# 0 and 0, the control 1.43 and 0.46 (widest, mean)
LIMITS = {"max_logit_gap": 0.5, "mean_logit_gap": 0.1}


def _config(name):
    return json.loads((ROOT / "bench/configs" / name).read_text())


def test_cell_resolves_from_files():
    cell = registry.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["runner"] == "offline_serving_arch"
    assert registry.load_runner(cell).run
    per_layer = {m.name for m in cell.per_layer}
    assert {"moe.gmm_roofline", "mla_moe.decode_roofline", "mfu.serve.mla_moe",
            "moe.tokens_per_expert", "decode_step_ms"} <= per_layer
    # the dense readers' counts would be wrong for this block
    assert not per_layer & {"decode_roofline", "mfu.serve", "flash_roofline"}
    assert {m.name for m in cell.end_to_end} == {"setup_s", "out_tok_s",
                                                 "tpot_p90_ms"}


def test_config_matches_published():
    config = _config("moonlight-16b-a3b-9l.json")
    cfg = arch_config(config)
    # every number of the published config is the file's own, but the depth
    for key, value in config["published"].items():
        assert config[key] == value or key in config["reduced"], key
    m, moe = cfg.mla, cfg.moe
    assert (cfg.n_layers, cfg.first_k_dense, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.d_ff, cfg.vocab, cfg.rope_theta) == (
        9, config["first_k_dense_replace"], 2048, 16, 16, 11264, 163840, 50000)
    assert (m.kv_rank, m.nope_dim, m.rope_dim, m.v_dim) == (
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"])
    assert (moe.n_experts, moe.top_k, moe.d_expert, moe.n_shared,
            moe.routed_scale, moe.scoring) == (
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["n_shared_experts"],
        config["routed_scaling_factor"], config["scoring_func"])


def test_work_counts_by_hand():
    cfg = arch_config(_config("moonlight-16b-a3b-9l.json"))
    # attention: wq 2048x16x192, wkv_a 2048x576, the latent's norm 512,
    # wkv_b 512x16x256, wo 16x128x2048; two norms of 2048 a layer
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    dense = attn + 3 * 2048 * 11264 + 2 * 2048
    moe = (attn + 2 * 2048 + 2048 * 64 + 64 + 64 * 3 * 2048 * 1408
           + 3 * 2048 * 2816)
    assert (dense, moe) == (82_973_184, 584_847_936)
    total = dense + 8 * moe + 2 * 163840 * 2048 + 2048
    assert F.params(cfg) == total == 5_432_847_360
    assert cfg.param_count() == total - 2048  # it leaves out the final norm
    assert F.latent_bytes_per_position(cfg) == 9 * 576 * 2 == 10_368
    assert F.pair_flops(cfg) == 6 * 2048 * 1408


@pytest.fixture(scope="module")
def tiny():
    from repro.models.model import Model

    cfg = arch_config(json.loads((DATA / "configs/tiny_v3.json").read_text()))
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")
    model = Model(cfg)
    params = W.make_params(cfg, jax.eval_shape(model.init, jax.random.key(0)),
                           SEED)
    return cfg, model, params


def test_weights_rule_covers_every_leaf(tiny):
    """Every leaf is drawn (none left at the program's init), each layer as
    the reference draws it alone."""
    cfg, model, params = tiny
    key = W.seed_key(SEED)
    for i in range(cfg.n_layers):
        want = W.layer_leaves(cfg, key, i, cfg.mlp_kind(i) == "dense")
        k = cfg.first_k_dense
        entry, g = (0, i) if i < k else (1, i - k)
        got = params["layers"][entry]
        flat = {"/".join(str(p.key) for p in path if hasattr(p, "key")): a[g]
                for path, a in jax.tree_util.tree_leaves_with_path(got)}
        flat = {n[:-2] if n.endswith("norm/w") or n.endswith("norm1/w")
                or n.endswith("norm2/w") else n: a for n, a in flat.items()}
        assert set(flat) == set(want), (set(flat) ^ set(want))
        for name, a in want.items():  # 1 + s*x may fuse differently: an ulp
            np.testing.assert_allclose(np.asarray(flat[name]), np.asarray(a),
                                       rtol=3e-7, atol=0)
    assert all(bool(jnp.any(a != 0)) and bool(jnp.any(a != 1))
               for a in jax.tree.leaves(params))


def test_reference_matches_program(tiny):
    """The bench's float32 reference and the program's full forward agree
    at float32 (both sum in another order: 1e-4)."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (2, 20)),
                         jnp.int32)
    want = model.logits(params, {"tokens": tokens})
    h = ref.hidden_from_seed(cfg, SEED, tokens)
    got = ref.final_logits(cfg, ref.head_from_seed(cfg, W.seed_key(SEED)), h)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    ctl = ref.final_logits(cfg, ref.head_from_seed(cfg, W.seed_key(SEED)),
                           ref.hidden_from_seed(cfg, SEED, tokens, "fp8"), "fp8")
    assert float(jnp.abs(ctl - want).max()) > 1e-2  # the control departs


def _tiny_cell(limits):
    bench = registry.load_benchmark()
    return registry.Cell(
        "tiny-v3", 1, json.loads((DATA / "configs/tiny_v3.json").read_text()),
        json.loads((DATA / "traffic/tiny_v3.json").read_text()),
        limits,
        registry._metrics(ROOT, bench["end_to_end"], CELL),
        registry._metrics(ROOT, bench["per_layer"], CELL))


@pytest.mark.parametrize("control", [False, True])
def test_sound_run_is_correct_and_control_is_not(control):
    devices = run.claim_devices(1, require_tpu=False)
    out = run.run_cell(_tiny_cell(LIMITS), SEED, 1.5, True, devices,
                       control=control)
    assert out["correct"] is not control, (out["checks"], out.get("readings"))
    assert out["attempted"] > 0
    names = set(out["metrics"])
    assert {"moe.tokens_per_expert", "engine.window_compiles"} <= names
    assert out["metrics"]["engine.window_compiles"]["value"] == 0
    assert 1 <= out["metrics"]["moe.tokens_per_expert"]["value"] <= 4 * 6
