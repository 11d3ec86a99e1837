"""The readers of the engine's host-time counters and of the prefill
program's device time, on made-up records, and on records of an engine
without those counters or of a run without the program."""
import pytest

from bench import registry

CELLS = ["yi-6b.decode_heavy", "yi-6b.prefill_heavy"]
NEW = ["engine.sync_ms", "engine.step_host_ms", "engine.admit_ms",
       "prefill_step_ms"]

# the engine's counters before the host-time counters existed
OLD_ENGINE = {"engine_steps": 200, "slot_steps": 1500,
              "wasted_slot_steps": 100, "prefills": 16, "cache_reloads": 0,
              "deadline_truncations": 0}
ENGINE = dict(OLD_ENGINE, run_s=5.0, admit_s=0.8, launch_s=0.1, sync_s=4.0,
              emit_s=0.06)
TRACE = {"programs": {"jit_prefill": [16, 2.4], "jit_decode_step": [200, 4.4],
                      "jit_insert": [16, 0.05]}}


def _readers(cell):
    return {m.name: m for m in registry.load_cell(cell).per_layer}


@pytest.mark.parametrize("cell", CELLS)
def test_new_metrics_load_for_cell(cell):
    metrics = _readers(cell)
    assert set(NEW) <= set(metrics)
    assert all(metrics[n].unit == "ms" and metrics[n].better == "lower"
               for n in NEW)


@pytest.mark.parametrize("name,want", [
    ("engine.sync_ms", 1e3 * 4.0 / 200),
    ("engine.step_host_ms", 1e3 * (0.1 + 0.06) / 200),
    ("engine.admit_ms", 1e3 * 0.8 / 16),
    ("prefill_step_ms", 1e3 * 2.4 / 16),
])
def test_reader_value(name, want):
    read = _readers(CELLS[0])[name].read
    assert read({"engine": ENGINE, "trace": TRACE}) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_none_without_counters_or_program(name):
    """A parent commit's engine has no host-time counters and its prefill
    program is ``jit__lambda``: every new reader stays silent there."""
    read = _readers(CELLS[1])[name].read
    parent_trace = {"programs": {"jit__lambda": [32, 2.5],
                                 "jit_decode_step": [200, 4.4]}}
    assert read({"engine": dict(OLD_ENGINE), "trace": parent_trace}) is None
    assert read({"engine": dict(OLD_ENGINE)}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_none_on_zero_count(name):
    read = _readers(CELLS[0])[name].read
    idle = dict(ENGINE, engine_steps=0, prefills=0, slot_steps=0,
                wasted_slot_steps=0)
    trace = {"programs": {"jit_prefill": [0, 0.0]}}
    assert read({"engine": idle, "trace": trace}) is None
