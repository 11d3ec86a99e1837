"""Abstract input specs (ShapeDtypeStruct) for every (arch × shape) cell.

Everything here is allocation-free: params/opt/cache structures come from
``jax.eval_shape``; batches are ShapeDtypeStructs. The dry-run lowers the
step functions against these and the production mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.model import Model

SHAPES: Dict[str, Dict[str, Any]] = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


def shape_applicable(cfg: ArchConfig, shape_name: str) -> Tuple[bool, str]:
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: O(s^2) — long_500k skipped (DESIGN §4)"
    return True, ""


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def batch_specs(cfg: ArchConfig, batch: int, seq: int) -> Dict[str, Any]:
    b: Dict[str, Any] = {
        "tokens": sds((batch, seq), jnp.int32),
        "labels": sds((batch, seq), jnp.int32),
    }
    if cfg.frontend == "audio":
        b["frames"] = sds((batch, cfg.n_frontend_tokens, cfg.d_model),
                          cfg.jnp_compute_dtype())
    if cfg.frontend == "vision":
        b["patches"] = sds((batch, cfg.n_frontend_tokens, cfg.d_model),
                           cfg.jnp_compute_dtype())
    return b


def infer_batch_specs(cfg: ArchConfig, batch: int, seq: int) -> Dict[str, Any]:
    b = batch_specs(cfg, batch, seq)
    b.pop("labels")
    return b


def abstract_params(model: Model):
    return jax.eval_shape(lambda: model.init(jax.random.key(0)))


def abstract_cache(model: Model, batch: int, cap: int):
    return jax.eval_shape(lambda: model.init_cache(batch, cap))


def decode_specs(cfg: ArchConfig, batch: int, cap: int) -> Dict[str, Any]:
    return {
        "tokens": sds((batch,), jnp.int32),
        "pos": sds((batch,), jnp.int32),
    }


def recommended_state_dtype(cfg: ArchConfig) -> str:
    """fp32 moments unless the arch can't fit them on a 256-chip pod."""
    n = cfg.param_count()
    # params(bf16) + m + v on 256 chips; leave most of the 16 GiB HBM for
    # gradients + activations + temp (EXPERIMENTS §Dry-run memory table)
    hbm = 16 * 1024**3
    if n * (2 + 8) / 256 < 0.30 * hbm:
        return "float32"
    if n * (2 + 4) / 256 < 0.40 * hbm:
        return "bfloat16"
    return "int8"
