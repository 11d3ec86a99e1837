"""Bring-up contract that holds off the chip: ``chip_smoke.py`` refuses to
report success without a TPU, the device table maps only kinds it knows,
and the compile cache sits where the path rule says."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro import hw
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, compile_cache_dir

ROOT = Path(__file__).resolve().parents[1]


def _run_smoke(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = _run_smoke(ROOT, env)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run_smoke(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_device_table():
    assert hw.target_for_device_kind("TPU v5 lite") is hw.TARGETS["tpu_v5e"]
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(ValueError, match="no hardware target"):
            hw.target_for_device_kind(kind)


def test_pickers_refuse_a_device_the_table_does_not_know():
    # the tests run on the CPU, which has no target: no silent v5e default
    with pytest.raises(ValueError, match="device kind 'cpu'"):
        hw.resolve_target(None)
    assert hw.resolve_target("tpu_v5e") is hw.TARGETS["tpu_v5e"]


def test_compile_cache_path_rule():
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None
    assert compile_cache_dir({}) == CHECKOUT_CACHE_DIR == str(ROOT / ".jax_cache")
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == CHECKOUT_CACHE_DIR
