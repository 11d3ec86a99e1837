"""Weights of a DeepSeek-V3-block model from the seed, made by the benchmark.

Every leaf of layer i is drawn from its own key: the seed's key folded with
i, then with a stable hash of the leaf's name (``mixer/wq``,
``mlp/shared/w1``, ...). So the program's whole tree comes out of one
jitted call, and the plain reference (``reference.py`` beside this file)
draws any one layer again, alone, and gets the same numbers.

Scales: 1/sqrt(fan-in) for every projection (an expert's by its own
width), 0.02 for the input embedding, RMSNorm gains 1 + 0.1 N(0, 1) (so a
dropped gain shows), and the selection bias 0.05 N(0, 1) (assumed: the
checkpoint's bias is learnt; on the scale of the sigmoid scores' spread it
moves some choices and not most).
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

GAIN, BIAS = 0.1, 0.05
_NORMS = ("norm1", "norm2", "norm_f", "kv_norm")


def seed_key(seed: int) -> jax.Array:
    """A key that tells apart every seed up to 2**64."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _scale(cfg, name: str, shape) -> Tuple[str, float]:
    """(kind, scale) of a leaf named by its path below the layer."""
    leaf = name.split("/")[-1]
    if leaf in _NORMS:
        return "gain", GAIN
    if leaf == "router_bias":
        return "normal", BIAS
    if name == "embed/tok":
        return "normal", 0.02
    if leaf in ("w2", "wo", "wkv_b", "head", "wq", "wkv_a", "w1", "w3", "router"):
        return "normal", shape[-2] ** -0.5  # fan-in
    raise ValueError(f"no weight rule for leaf {name!r}")


def _draw(cfg, key, name: str, shape, dtype) -> jax.Array:
    kind, scale = _scale(cfg, name, shape)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    return (1.0 + scale * x if kind == "gain" else scale * x).astype(dtype)


def layer_leaf(cfg, key, name: str, layer, shape, dtype) -> jax.Array:
    return _draw(cfg, jax.random.fold_in(key, layer), name, shape, dtype)


def _name(keypath) -> str:
    """``mixer/wq``-style name; a norm's gain is named by its norm."""
    parts = [str(k.key) for k in keypath if isinstance(k, jax.tree_util.DictKey)]
    if len(parts) > 1 and parts[-1] == "w" and parts[-2] in _NORMS:
        parts.pop()
    return "/".join(parts)


def first_layers(cfg):
    """The first layer of each entry of the program's stack tuple, and its
    period: the leading dense layers (one entry), then the pattern."""
    lead = [(0, 1)] if cfg.first_k_dense else []
    return lead + [(cfg.first_k_dense + j, cfg.period)
                   for j in range(len(cfg.pattern()))]


def make_params(cfg, abstract, seed: int):
    """The program's parameter tree (``abstract``: its shapes) filled from
    ``seed`` in one jitted call."""
    if cfg.mla is None or cfg.moe is None or cfg.encoder_decoder or cfg.frontend:
        raise ValueError(f"{cfg.name}: these weights cover the DeepSeek-V3 "
                         "block only")
    starts = first_layers(cfg)

    def build(key):
        def leaf(keypath, a):
            name = _name(keypath)
            if not name.startswith("layers/"):
                return _draw(cfg, key, name, a.shape, a.dtype)
            first, period = starts[keypath[1].idx]
            layers = first + period * jnp.arange(a.shape[0])
            return jax.vmap(lambda i: layer_leaf(
                cfg, key, name[len("layers/"):], i, a.shape[1:], a.dtype))(layers)

        return jax.tree_util.tree_map_with_path(leaf, abstract)

    return jax.jit(build)(seed_key(seed))


def layer_shapes(cfg, dense: bool) -> Dict[str, tuple]:
    """Leaves of one layer (a dense or an MoE one), by name."""
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    out = {"norm1": (d,), "norm2": (d,),
           "mixer/wq": (d, h * m.qk_dim), "mixer/wkv_a": (d, m.cache_dim),
           "mixer/kv_norm": (m.kv_rank,),
           "mixer/wkv_b": (m.kv_rank, h * (m.nope_dim + m.v_dim)),
           "mixer/wo": (h * m.v_dim, d)}
    if dense:
        f = cfg.d_ff
        return {**out, "mlp/w1": (d, f), "mlp/w3": (d, f), "mlp/w2": (f, d)}
    e, f, fs = cfg.moe.n_experts, cfg.moe.d_expert, cfg.moe.n_shared * cfg.moe.d_expert
    return {**out, "mlp/router": (d, e), "mlp/router_bias": (e,),
            "mlp/w1": (e, d, f), "mlp/w3": (e, d, f), "mlp/w2": (e, f, d),
            "mlp/shared/w1": (d, fs), "mlp/shared/w3": (d, fs),
            "mlp/shared/w2": (fs, d)}


def layer_leaves(cfg, key, layer, dense: bool,
                 dtype=jnp.float32) -> Dict[str, jax.Array]:
    """One layer's leaves, drawn again in the parameter dtype and upcast
    (the selection bias is float32 in the program)."""
    pd = cfg.jnp_param_dtype()
    return {name: layer_leaf(cfg, key, name, layer, shape,
                             jnp.float32 if name.endswith("bias") else pd
                             ).astype(dtype)
            for name, shape in layer_shapes(cfg, dense).items()}


def top_leaves(cfg, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
    pd = cfg.jnp_param_dtype()
    v, d = cfg.vocab, cfg.d_model
    return {"tok": _draw(cfg, key, "embed/tok", (v, d), pd).astype(dtype),
            "head": _draw(cfg, key, "embed/head", (d, v), pd).astype(dtype),
            "norm_f": _draw(cfg, key, "norm_f", (d,), pd).astype(dtype)}
