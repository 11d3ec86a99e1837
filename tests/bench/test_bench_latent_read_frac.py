"""The per-layer metric ``mla.latent_read_frac``: registered for the
moonlight cell, read from the engine's latent-row counters, absent where
the engine keeps none, and present in a whole run of a small cell of the
DeepSeek-V3 block on the CPU."""
import json
from pathlib import Path

import pytest

from bench import registry, run

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
CELL = "moonlight-16b-a3b-9l.decode_heavy_64"
METRIC = "mla.latent_read_frac"
SEED = 2**31 + 5
# the small cell's limits, as tests/bench/test_bench_moonlight.py sets them
LIMITS = {"max_logit_gap": 0.5, "mean_logit_gap": 0.1}


def _reader():
    return {m.name: m.read for m in registry.load_cell(CELL).per_layer}[METRIC]


def test_registered_for_the_moonlight_cell_only():
    bench = registry.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[METRIC]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tpot_p90_ms"
    assert METRIC in {m.name for m in registry.load_cell(CELL).per_layer}


@pytest.mark.parametrize("engine", [
    {"engine_steps": 3, "moe_pairs": 7},            # a program without the counters
    {"latent_rows_read": 0, "latent_rows_cap": 0},  # no decode step ran
], ids=["absent", "empty"])
def test_reads_none_without_the_counters(engine):
    assert _reader()({"engine": engine}) is None


def test_reads_rows_read_over_rows_cap():
    assert _reader()({"engine": {"latent_rows_read": 512,
                                 "latent_rows_cap": 4096}}) == 0.125


def test_small_cell_reports_the_share():
    bench = registry.load_benchmark()
    cell = registry.Cell(
        "tiny-v3", 1, json.loads((DATA / "configs/tiny_v3.json").read_text()),
        json.loads((DATA / "traffic/tiny_v3.json").read_text()),
        LIMITS,
        registry._metrics(ROOT, bench["end_to_end"], CELL),
        registry._metrics(ROOT, bench["per_layer"], CELL))
    devices = run.claim_devices(1, require_tpu=False)
    out = run.run_cell(cell, SEED, 1.5, True, devices)
    assert out["correct"], (out["checks"], out.get("readings"))
    assert 0 < out["metrics"][METRIC]["value"] <= 1
