"""Heterogeneous block stacking.

A stack is grouped by the config's block-pattern *period*: layer i uses
pattern position ``i % period``; parameters for each period position are
stacked over the ``n_layers/period`` groups and the whole stack runs as one
``lax.scan`` over groups (HLO size stays O(period) regardless of depth — the
94-layer dry-runs depend on this), with per-group remat.

Leading layers of another kind (``first_k_dense``, DeepSeek-V3's dense
layers before the MoE ones) are a segment of their own, scanned before
the repeating pattern: the stack's tuple holds the leading segment's
entries, then the pattern's.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mla as mla_mod
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
    elif mixer_kind == "mla":
        p["mixer"] = mla_mod.init_mla(cfg, ks[0])
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


# The positions axis of each cache leaf of one block ([B, ...], before the
# group axis is stacked in front), per mixer kind: prefill pads these to the
# capacity and a decode step writes its row into them at ``pos``. A leaf not
# named holds no positions: a recurrent state (replaced whole by a step) or
# a cross cache (fixed by the encoder).
CACHE_POSITIONS = {"attention": {"k": 2, "v": 2}, "mla": {"ckv": 1}}


def init_block_cache(cfg, mixer_kind: str, batch: int, cap: int,
                     cross_len: int = 0) -> Dict:
    """Zeroed decode cache for one block."""
    dt = cfg.jnp_compute_dtype()
    c: Dict[str, Any] = {}
    if mixer_kind == "attention":
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        c["k"] = jnp.zeros((batch, hkv, cap, dh), dt)
        c["v"] = jnp.zeros((batch, hkv, cap, dh), dt)
    elif mixer_kind == "mla":
        c["ckv"] = jnp.zeros((batch, cap, cfg.mla.cache_dim), dt)
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


def _apply_mlp(cfg, p, mlp_kind, x, live=None):
    """(x after the MLP, aux loss, MoE counters [moe.N_STATS])."""
    none = jnp.zeros((), jnp.float32), jnp.zeros((moe_mod.N_STATS,), jnp.int32)
    if mlp_kind == "none":
        return (x,) + none
    h = L.apply_norm(cfg, p["norm2"], x)
    if mlp_kind == "dense":
        return (x + L.apply_dense_mlp(cfg, p["mlp"], h),) + none
    y, aux, stats = moe_mod.apply_moe(cfg, p["mlp"], h, live)
    return x + y, aux, stats


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
    elif mixer_kind == "mla":
        y, cache["ckv"] = mla_mod.mla_forward(cfg, p["mixer"], h, positions)
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
    x, aux, _ = _apply_mlp(cfg, p, mlp_kind, x)
    return x, cache, aux


def apply_block_decode(
    cfg,
    p: Dict,
    kinds: Tuple[str, str],
    x: jax.Array,  # [B, 1, D]
    cache: Dict,
    pos: jax.Array,
    layer: jax.Array,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict, jax.Array]:
    """One token through one block at group ``layer``; the block's cache is
    only read. The leaves ``_WHOLE_CACHE`` names are the whole stack's
    [G, B, ...], the others the group's.
    Returns (x, new, MoE counters): what the step changes in the cache —
    an attention mixer's new key and value (``k``/``v`` [B, Hkv, 1, dh]) or
    a latent mixer's new row (``ckv`` [B, 1, kv_rank + rope_dim]), for
    ``write_decode_cache`` to write at ``pos``, or a recurrent mixer's
    whole new state. Cross-attention's ``xk``/``xv`` never change. The
    counters count the rows ``live`` marks (all when None)."""
    mixer_kind, mlp_kind = kinds
    h = L.apply_norm(cfg, p["norm1"], x)
    if mixer_kind == "attention":
        y, k, v = attn.decode_attention(cfg, p["mixer"], h, cache["k"],
                                        cache["v"], pos)
        new = {"k": k, "v": v}
    elif mixer_kind == "mla":
        y, ckv = mla_mod.mla_decode(cfg, p["mixer"], h, cache["ckv"], pos,
                                    layer)
        new = {"ckv": ckv}
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
    x, _aux, stats = _apply_mlp(cfg, p, mlp_kind, x, live)
    return x, new, stats


def _write_tokens(cache: jax.Array, tokens: jax.Array, pos: jax.Array,
                  axis: int) -> jax.Array:
    """``tokens`` [G, B, ...] (length 1 on ``axis``) written into ``cache``
    [G, B, ...] (length CAP on ``axis``) at each row's position ``pos``
    [B]: one ``dynamic_update_slice`` per row, which XLA does in place in a
    donated cache. (For the TPU, a scatter over rows and positions compiles
    to copies of the cache instead.)"""
    start = [0] * cache.ndim
    for r in range(cache.shape[1]):
        start[1], start[axis] = r, pos[r]
        cache = jax.lax.dynamic_update_slice(cache, tokens[:, r: r + 1],
                                             tuple(start))
    return cache


def write_decode_cache(mixer_kind: str, cache: Dict, new: Dict,
                       pos: jax.Array) -> Dict:
    """A block's stacked cache after a decode step, given the step's
    ``new`` (stacked over groups by the scan): a leaf with positions
    (``CACHE_POSITIONS``) takes the new row at ``pos``, a recurrent state is
    replaced whole, the rest (cross caches) stays as it was."""
    out = dict(cache)
    axes = CACHE_POSITIONS.get(mixer_kind, {})
    for key, val in new.items():
        out[key] = (val if key not in axes
                    else _write_tokens(cache[key], val, pos, axes[key] + 1))
    return out


def pad_positions(mixer_kind: str, cache: Dict, cap: int) -> Dict:
    """A block's stacked cache from prefill, its leaves with positions
    (``CACHE_POSITIONS``) padded to the capacity ``cap``."""
    out = dict(cache)
    for key, axis in CACHE_POSITIONS.get(mixer_kind, {}).items():
        if key in cache:
            pad = [(0, 0)] * cache[key].ndim
            pad[axis + 1] = (0, cap - cache[key].shape[axis + 1])
            out[key] = jnp.pad(cache[key], pad)
    return out


# ---------------------------------------------------------------------------
# scanned stack
# ---------------------------------------------------------------------------


def segments(cfg, n_layers: Optional[int] = None,
             pattern: Optional[Tuple[Tuple[str, str], ...]] = None):
    """[(pattern, groups)] of a stack, in order: the leading dense layers
    (if the config has them), then the repeating pattern. An explicit
    ``pattern`` (the encoder's) has no leading layers."""
    n_layers = n_layers or cfg.n_layers
    segs = []
    if pattern is None and cfg.first_k_dense:
        segs.append((cfg.lead_pattern(), cfg.first_k_dense))
        n_layers -= cfg.first_k_dense
    pattern = pattern or cfg.pattern()
    assert n_layers % len(pattern) == 0, (n_layers, len(pattern))
    segs.append((pattern, n_layers // len(pattern)))
    return segs


_EXPERT_LEAVES = ("w1", "w2", "w3")


def _scan_split(pattern, seg_params: Tuple):
    """(scanned, whole): the segment's params with the expert weights of its
    MoE blocks taken out of what the scan slices (``whole``, [G, E, ...]).
    The grouped matmul reads them where they lie, at the group's index; a
    slice taken by the scan would be copied first, for a custom call."""
    scanned, whole = [], []
    for kinds, p in zip(pattern, seg_params):
        w = {}
        if kinds[1] == "moe":
            mlp = dict(p["mlp"])
            w = {k: mlp.pop(k) for k in _EXPERT_LEAVES if k in mlp}
            p = {**p, "mlp": mlp}
        scanned.append(p)
        whole.append(w)
    return tuple(scanned), tuple(whole)


# Cache leaves a decode kernel reads where they lie in the stack, at the
# group's index: the scan does not slice them (it would copy the slice
# first, for the kernel's custom call).
_WHOLE_CACHE = {"mla": ("ckv",)}


def _cache_split(pattern, seg_cache: Tuple):
    """(scanned, whole): the segment's cache with the leaves
    ``_WHOLE_CACHE`` names taken out of what the scan slices."""
    scanned, whole = [], []
    for kinds, c in zip(pattern, seg_cache):
        keys = _WHOLE_CACHE.get(kinds[0], ())
        scanned.append({k: v for k, v in c.items() if k not in keys})
        whole.append({k: v for k, v in c.items() if k in keys})
    return tuple(scanned), tuple(whole)


def _joined(p: Dict, whole: Dict, i: jax.Array) -> Dict:
    """A block's params at group ``i`` from the scanned slice and the whole
    expert weights."""
    return {**p, "mlp": {**p["mlp"], **whole, "layer": i}} if whole else p


def _split(stack: Tuple, segs):
    """The stack's tuple cut into one tuple per segment."""
    out, i = [], 0
    for pattern, _ in segs:
        out.append(stack[i: i + len(pattern)])
        i += len(pattern)
    return out


def init_stack(cfg, rng, n_layers: Optional[int] = None, cross: bool = False,
               pattern: Optional[Tuple[Tuple[str, str], ...]] = None) -> Tuple:
    """Returns a tuple (one entry per period position of each segment) of
    param pytrees with leading group dim G (the segment's groups)."""
    segs = segments(cfg, n_layers, pattern)
    out = []
    for si, (pat, g) in enumerate(segs):
        # the repeating pattern (the last segment) draws as it always has
        salt = (len(segs) - 1 - si) * 10_000
        for pp, kinds in enumerate(pat):
            keys = jax.random.split(jax.random.fold_in(rng, salt + pp), g)
            out.append(
                jax.vmap(
                    lambda k_: init_block(cfg, k_, kinds[0], kinds[1], cross=cross)
                )(keys)
            )
    return tuple(out)


def init_stack_cache(cfg, batch: int, cap: int, n_layers: Optional[int] = None,
                     cross_len: int = 0,
                     pattern: Optional[Tuple[Tuple[str, str], ...]] = None) -> Tuple:
    def rep(tree, g):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (g,) + a.shape), tree)

    return tuple(
        rep(init_block_cache(cfg, kinds[0], batch, cap, cross_len=cross_len), g)
        for pat, g in segments(cfg, n_layers, pattern) for kinds in pat
    )


def stack_kinds(cfg, pattern: Optional[Tuple[Tuple[str, str], ...]] = None):
    """(mixer, mlp) of each entry of the stack's tuple."""
    return tuple(k for pat, _ in segments(cfg, None, pattern) for k in pat)


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
    """Scan over layer groups, segment by segment. Returns (x, caches (if
    collected), aux_sum)."""
    remat = cfg.remat_stack if remat is None else remat
    segs = segments(cfg, None, pattern)
    aux = jnp.zeros((), jnp.float32)
    caches = []
    for (pat, _), seg_params in zip(segs, _split(stack_params, segs)):
        scanned, whole = _scan_split(pat, seg_params)

        def body(carry, params_g, pat=pat, whole=whole):
            xc, aux, i = carry
            xc = pctx.constrain_tokens(xc)
            out = []
            for pp, kinds in enumerate(pat):
                xc, cache, a = apply_block(cfg, _joined(params_g[pp], whole[pp], i),
                                           kinds, xc, positions, causal=causal,
                                           enc_out=enc_out)
                xc = pctx.constrain_tokens(xc)
                out.append(cache)
                aux = aux + a
            return (xc, aux, i + 1), (tuple(out) if collect_cache else None)

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable
            )
        (x, aux, _), seg_caches = jax.lax.scan(
            body, (x, aux, jnp.zeros((), jnp.int32)), scanned)
        if collect_cache:
            caches.extend(seg_caches)
    return x, (tuple(caches) if collect_cache else None), aux


def apply_stack_decode(
    cfg,
    stack_params: Tuple,
    x: jax.Array,
    cache: Tuple,
    pos: jax.Array,
    pattern: Optional[Tuple[Tuple[str, str], ...]] = None,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple, jax.Array]:
    """One decode step through the scanned stack. ``pos`` [B] holds each
    row's depth; it is closed over by the scan body and handled in the
    mixers' decode (SSM/xLSTM mixers are position-free recurrences).

    The scan reads each group's cache and carries out only what the step
    changes (``apply_block_decode``); the new keys and values of every
    layer are written after it, so a donated cache is updated in place and
    no layer's slice of it is copied. The latent cache is not sliced by the
    scan at all (``_WHOLE_CACHE``): its kernel reads it at the group's
    index. Returns (x, cache, the MoE counters summed over layers, counting
    the rows ``live`` marks)."""
    segs = segments(cfg, None, pattern)
    stats = jnp.zeros((moe_mod.N_STATS,), jnp.int32)
    out = []
    for (pat, _), seg_params, seg_cache in zip(segs, _split(stack_params, segs),
                                               _split(cache, segs)):

        scanned, whole = _scan_split(pat, seg_params)
        scanned_c, whole_c = _cache_split(pat, seg_cache)

        def body(carry, inp, pat=pat, whole=whole, whole_c=whole_c):
            xc, st, i = carry
            params_g, cache_g = inp
            new = []
            for pp, kinds in enumerate(pat):
                xc, n, s_ = apply_block_decode(
                    cfg, _joined(params_g[pp], whole[pp], i), kinds, xc,
                    {**cache_g[pp], **whole_c[pp]}, pos, i, live)
                new.append(n)
                st = st + s_
            return (xc, st, i + 1), tuple(new)

        (x, stats, _), new = jax.lax.scan(
            body, (x, stats, jnp.zeros((), jnp.int32)), (scanned, scanned_c))
        out.extend(write_decode_cache(kinds[0], c, n, pos)
                   for kinds, c, n in zip(pat, seg_cache, new))
    return x, tuple(out), stats
