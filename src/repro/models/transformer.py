"""Heterogeneous block stacking.

A stack is grouped by the config's block-pattern *period*: layer i uses
pattern position ``i % period``; parameters for each period position are
stacked over the ``n_layers/period`` groups and the whole stack runs as one
``lax.scan`` over groups (HLO size stays O(period) regardless of depth — the
94-layer dry-runs depend on this), with per-group remat.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.parallel import context as pctx


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------


def init_block(cfg, rng, mixer_kind: str, mlp_kind: str, cross: bool = False) -> Dict:
    ks = jax.random.split(rng, 6)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg)}
    if mixer_kind == "attention":
        p["mixer"] = attn.init_attention(cfg, ks[0])
    elif mixer_kind == "mamba":
        p["mixer"] = ssm_mod.init_mamba(cfg, ks[0])
    elif mixer_kind == "mlstm":
        p["mixer"] = xlstm_mod.init_mlstm(cfg, ks[0])
    elif mixer_kind == "slstm":
        p["mixer"] = xlstm_mod.init_slstm(cfg, ks[0])
    else:
        raise ValueError(mixer_kind)
    if cross:
        p["norm_x"] = L.init_norm(cfg)
        p["xattn"] = attn.init_attention(cfg, ks[1])
    if mlp_kind == "dense":
        p["norm2"] = L.init_norm(cfg)
        p["mlp"] = L.init_dense_mlp(cfg, ks[2])
    elif mlp_kind == "moe":
        p["norm2"] = L.init_norm(cfg)
        p["mlp"] = moe_mod.init_moe(cfg, ks[2])
    return p


def init_block_cache(cfg, mixer_kind: str, batch: int, cap: int,
                     cross_len: int = 0) -> Dict:
    """Zeroed decode cache for one block."""
    dt = cfg.jnp_compute_dtype()
    c: Dict[str, Any] = {}
    if mixer_kind == "attention":
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        c["k"] = jnp.zeros((batch, hkv, cap, dh), dt)
        c["v"] = jnp.zeros((batch, hkv, cap, dh), dt)
    elif mixer_kind == "mamba":
        c.update(ssm_mod.init_mamba_cache(cfg, batch, dt))
    elif mixer_kind == "mlstm":
        c.update(xlstm_mod.init_mlstm_cache(cfg, batch))
    elif mixer_kind == "slstm":
        c.update(xlstm_mod.init_slstm_cache(cfg, batch))
    if cross_len:
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        c["xk"] = jnp.zeros((batch, hkv, cross_len, dh), dt)
        c["xv"] = jnp.zeros((batch, hkv, cross_len, dh), dt)
    return c


def _apply_mlp(cfg, p, mlp_kind, x):
    if mlp_kind == "none":
        return x, jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg, p["norm2"], x)
    if mlp_kind == "dense":
        return x + L.apply_dense_mlp(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)
    y, aux = moe_mod.apply_moe(cfg, p["mlp"], h)
    return x + y, aux


def apply_block(
    cfg,
    p: Dict,
    kinds: Tuple[str, str],
    x: jax.Array,
    positions: jax.Array,
    causal: bool = True,
    enc_out: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, jax.Array]:
    """Train/prefill. Returns (x, cache_contrib, aux_loss). cache_contrib has
    the same structure as init_block_cache (attention k/v filled from this
    forward; SSM states = final states) so prefill can build a decode cache."""
    mixer_kind, mlp_kind = kinds
    h = L.apply_norm(cfg, p["norm1"], x)
    cache: Dict[str, Any] = {}
    if mixer_kind == "attention":
        y, (k, v) = attn.attention_forward(cfg, p["mixer"], h, positions,
                                           causal=causal)
        cache["k"], cache["v"] = k.astype(cfg.jnp_compute_dtype()), v.astype(
            cfg.jnp_compute_dtype()
        )
        x = x + y
    elif mixer_kind == "mamba":
        y, st = ssm_mod.mamba_forward(cfg, p["mixer"], h, return_state=True)
        cache.update(st)
        x = x + y
    elif mixer_kind == "mlstm":
        y, st = xlstm_mod.mlstm_forward(cfg, p["mixer"], h, return_state=True)
        cache.update(st)
        x = x + y
    elif mixer_kind == "slstm":
        y, st = xlstm_mod.slstm_forward(cfg, p["mixer"], h, return_state=True)
        cache.update(st)
        x = x + y
    if enc_out is not None and "xattn" in p:
        hx = L.apply_norm(cfg, p["norm_x"], x)
        y, (xk, xv) = attn.attention_forward(cfg, p["xattn"], hx, positions,
                                             causal=False, kv_x=enc_out,
                                             use_rope=False)
        cache["xk"], cache["xv"] = xk.astype(cfg.jnp_compute_dtype()), xv.astype(
            cfg.jnp_compute_dtype()
        )
        x = x + y
    x, aux = _apply_mlp(cfg, p, mlp_kind, x)
    return x, cache, aux


def apply_block_decode(
    cfg,
    p: Dict,
    kinds: Tuple[str, str],
    x: jax.Array,  # [B, 1, D]
    cache: Dict,
    pos: jax.Array,
) -> Tuple[jax.Array, Dict]:
    """One token through one block; the block's cache is only read.
    Returns (x, new): what the step changes in the cache — an attention
    mixer's new key and value (``k``/``v`` [B, Hkv, 1, dh], for
    ``write_decode_cache`` to write at ``pos``), or a recurrent mixer's
    whole new state. Cross-attention's ``xk``/``xv`` never change."""
    mixer_kind, mlp_kind = kinds
    h = L.apply_norm(cfg, p["norm1"], x)
    if mixer_kind == "attention":
        y, k, v = attn.decode_attention(cfg, p["mixer"], h, cache["k"],
                                        cache["v"], pos)
        new = {"k": k, "v": v}
    elif mixer_kind == "mamba":
        y, new = ssm_mod.mamba_decode(cfg, p["mixer"], h,
                                      {"conv": cache["conv"], "h": cache["h"]})
    elif mixer_kind == "mlstm":
        y, new = xlstm_mod.mlstm_decode(
            cfg, p["mixer"], h, {k_: cache[k_] for k_ in ("C", "n", "m")}
        )
    elif mixer_kind == "slstm":
        y, new = xlstm_mod.slstm_decode(
            cfg, p["mixer"], h, {k_: cache[k_] for k_ in ("c", "n", "h", "m")}
        )
    else:
        raise ValueError(mixer_kind)
    x = x + y
    if "xattn" in p:
        hx = L.apply_norm(cfg, p["norm_x"], x)
        y, _, _ = attn.decode_attention(cfg, p["xattn"], hx, cache["xk"],
                                        cache["xv"], pos, cross=True)
        x = x + y
    x, _aux = _apply_mlp(cfg, p, mlp_kind, x)
    return x, new


def _write_tokens(cache: jax.Array, tokens: jax.Array, pos: jax.Array) -> jax.Array:
    """``tokens`` [G, B, Hkv, 1, dh] written into ``cache`` [G, B, Hkv, CAP,
    dh] at each row's position: one ``dynamic_update_slice`` per row (once
    at a scalar ``pos``), which XLA does in place in a donated cache. (For
    the TPU, a scatter over rows and positions compiles to copies of the
    cache instead.)"""
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, tokens, (0, 0, 0, pos, 0))
    for r in range(cache.shape[1]):
        cache = jax.lax.dynamic_update_slice(cache, tokens[:, r: r + 1],
                                             (0, r, 0, pos[r], 0))
    return cache


def write_decode_cache(mixer_kind: str, cache: Dict, new: Dict,
                       pos: jax.Array) -> Dict:
    """A block's stacked cache after a decode step, given the step's
    ``new`` (stacked over groups by the scan): attention keys and values go
    in at ``pos``, a recurrent state is replaced whole, the rest (cross
    caches) stays as it was."""
    out = dict(cache)
    for key, val in new.items():
        out[key] = (_write_tokens(cache[key], val, pos)
                    if mixer_kind == "attention" else val)
    return out


# ---------------------------------------------------------------------------
# scanned stack
# ---------------------------------------------------------------------------


def init_stack(cfg, rng, n_layers: Optional[int] = None, cross: bool = False,
               pattern: Optional[Tuple[Tuple[str, str], ...]] = None) -> Tuple:
    """Returns a tuple (one entry per period position) of param pytrees with
    leading group dim G = n_layers / period."""
    pattern = pattern or cfg.pattern()
    n_layers = n_layers or cfg.n_layers
    period = len(pattern)
    assert n_layers % period == 0, (n_layers, period)
    g = n_layers // period
    out = []
    for pp, kinds in enumerate(pattern):
        keys = jax.random.split(jax.random.fold_in(rng, pp), g)
        out.append(
            jax.vmap(
                lambda k_: init_block(cfg, k_, kinds[0], kinds[1], cross=cross)
            )(keys)
        )
    return tuple(out)


def init_stack_cache(cfg, batch: int, cap: int, n_layers: Optional[int] = None,
                     cross_len: int = 0,
                     pattern: Optional[Tuple[Tuple[str, str], ...]] = None) -> Tuple:
    pattern = pattern or cfg.pattern()
    n_layers = n_layers or cfg.n_layers
    g = n_layers // len(pattern)

    def rep(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (g,) + a.shape), tree)

    return tuple(
        rep(init_block_cache(cfg, kinds[0], batch, cap, cross_len=cross_len))
        for kinds in pattern
    )


def apply_stack(
    cfg,
    stack_params: Tuple,
    x: jax.Array,
    positions: jax.Array,
    causal: bool = True,
    enc_out: Optional[jax.Array] = None,
    pattern: Optional[Tuple[Tuple[str, str], ...]] = None,
    remat: Optional[bool] = None,
    collect_cache: bool = False,
) -> Tuple[jax.Array, Optional[Tuple], jax.Array]:
    """Scan over layer groups. Returns (x, caches (if collected), aux_sum)."""
    pattern = pattern or cfg.pattern()
    remat = cfg.remat_stack if remat is None else remat

    def body(carry, params_g):
        xc, aux = carry
        xc = pctx.constrain_tokens(xc)
        caches = []
        for pp, kinds in enumerate(pattern):
            xc, cache, a = apply_block(cfg, params_g[pp], kinds, xc, positions,
                                       causal=causal, enc_out=enc_out)
            xc = pctx.constrain_tokens(xc)
            caches.append(cache)
            aux = aux + a
        out = tuple(caches) if collect_cache else None
        return (xc, aux), out

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    stack_params)
    return x, caches, aux


def apply_stack_decode(
    cfg,
    stack_params: Tuple,
    x: jax.Array,
    cache: Tuple,
    pos: jax.Array,
    pattern: Optional[Tuple[Tuple[str, str], ...]] = None,
) -> Tuple[jax.Array, Tuple]:
    """One decode step through the scanned stack. ``pos`` is a scalar (all
    rows at the same depth) or ``[B]`` vector (per-slot depths); it is
    closed over by the scan body and handled in ``attn.decode_attention``
    (SSM/xLSTM mixers are position-free recurrences).

    The scan reads each group's cache and carries out only what the step
    changes (``apply_block_decode``); the new keys and values of every
    layer are written after it, so a donated cache is updated in place and
    no layer's slice of it is copied."""
    pattern = pattern or cfg.pattern()

    def body(xc, inp):
        params_g, cache_g = inp
        new = []
        for pp, kinds in enumerate(pattern):
            xc, n = apply_block_decode(cfg, params_g[pp], kinds, xc,
                                       cache_g[pp], pos)
            new.append(n)
        return xc, tuple(new)

    x, new = jax.lax.scan(body, x, (stack_params, cache))
    return x, tuple(write_decode_cache(kinds[0], c, n, pos)
                    for kinds, c, n in zip(pattern, cache, new))
