"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434; the attention
of DeepSeek-V3), without a query latent (``q_lora_rank`` null).

Per position, ``wkv_a`` gives a latent of width ``kv_rank``, RMS-normalised
(``kv_norm``), and one rope key of width ``rope_dim`` shared by every head.
``wkv_b`` expands the latent into each head's key (``nope_dim``, beside the
rope key) and value (``v_dim``). Scores are scaled by 1/sqrt(nope + rope).

The cache holds, per position, only the normalised latent and the roped
key (``ckv`` [B, CAP, kv_rank + rope_dim]). Prefill expands them per head
and attends with the flash kernel (values zero-padded to the key width).
Decode attends in latent form: the key half of ``wkv_b`` is folded into the
query and the value half into the output, so the cache is never expanded.
On a TPU the cache is read by the ``latent_decode`` kernel
(``kernels/latent_decode.py``), each row's blocks below its position once;
elsewhere by the einsums below over all ``CAP`` rows, the reference.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import latent_decode as kernel
from repro.models import attention as attn
from repro.models import layers as L

NEG_INF = attn.NEG_INF


def init_mla(cfg, rng) -> Dict:
    m, h, d = cfg.mla, cfg.n_heads, cfg.d_model
    dt = cfg.jnp_param_dtype()
    ks = jax.random.split(rng, 4)
    return {
        "wq": L.normal(ks[0], (d, h * m.qk_dim), d ** -0.5, dt),
        "wkv_a": L.normal(ks[1], (d, m.cache_dim), d ** -0.5, dt),
        "kv_norm": {"w": jnp.ones((m.kv_rank,), dt)},
        "wkv_b": L.normal(ks[2], (m.kv_rank, h * (m.nope_dim + m.v_dim)),
                          m.kv_rank ** -0.5, dt),
        "wo": L.normal(ks[3], (h * m.v_dim, d), (h * m.v_dim) ** -0.5, dt),
    }


def _queries(cfg, p, x, sin, cos):
    """x [B, S, D] -> q [B, S, H, nope + rope], its rope part rotated."""
    m = cfg.mla
    b, s, _ = x.shape
    cd = cfg.jnp_compute_dtype()
    q = (x.astype(cd) @ p["wq"].astype(cd)).reshape(b, s, cfg.n_heads, m.qk_dim)
    q_pe = L.apply_rope(q[..., m.nope_dim:], sin[..., None, :], cos[..., None, :])
    return jnp.concatenate([q[..., :m.nope_dim], q_pe], -1)


def latent(cfg, p, x, sin, cos):
    """x [B, S, D] -> what the cache holds [B, S, kv_rank + rope_dim]: the
    normalised latent, then the roped key."""
    m = cfg.mla
    cd = cfg.jnp_compute_dtype()
    kv = x.astype(cd) @ p["wkv_a"].astype(cd)
    c = L.apply_norm(cfg, p["kv_norm"], kv[..., :m.kv_rank])
    kr = L.apply_rope(kv[..., m.kv_rank:], sin, cos)
    return jnp.concatenate([c, kr], -1).astype(cd)


def mla_forward(cfg, p: Dict, x: jax.Array, positions: jax.Array,
                chunked_threshold: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """Train/prefill over x [B, S, D] at ``positions`` [S]. Returns (output
    [B, S, D], ckv [B, S, kv_rank + rope_dim] for the cache)."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    cd = cfg.jnp_compute_dtype()
    sin, cos = L.rope_tables(cfg, positions, m.rope_dim)  # [S, rope/2]
    q = _queries(cfg, p, x, sin, cos)
    ckv = latent(cfg, p, x, sin, cos)
    kv = (ckv[..., :m.kv_rank] @ p["wkv_b"].astype(cd)).reshape(
        b, s, h, m.nope_dim + m.v_dim)
    kr = jnp.broadcast_to(ckv[:, :, None, m.kv_rank:], (b, s, h, m.rope_dim))
    k = jnp.concatenate([kv[..., :m.nope_dim], kr], -1)
    v = jnp.pad(kv[..., m.nope_dim:], ((0, 0),) * 3 + ((0, m.qk_dim - m.v_dim),))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [B, H, S, qk]
    if s >= chunked_threshold:
        out = attn.chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    else:
        out = attn.flash_attention(q, k, v, True, cfg.attn_chunk)
    out = out[..., :m.v_dim].transpose(0, 2, 1, 3).reshape(b, s, h * m.v_dim)
    y = out.astype(cd) @ p["wo"].astype(cd)
    return y.astype(x.dtype), ckv


def mla_decode(cfg, p: Dict, x: jax.Array, cache: jax.Array, pos: jax.Array,
               layer: jax.Array):
    """One token x [B, 1, D] over the layer's latent cache at ``pos`` [B]
    (each row's own depth): ``cache`` is the whole stack [G, B,
    CAP, kv_rank + rope_dim] and ``layer`` the layer's index in it. The
    cache is only read: the token attends over its rows ``< pos`` and over
    its own latent, which stands in for the row at ``pos``. Returns (y [B,
    1, D], the token's row [B, 1, kv_rank + rope_dim] for the caller to
    write at ``pos``)."""
    m, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    cd = cfg.jnp_compute_dtype()
    r = m.kv_rank
    sin, cos = L.rope_tables(cfg, pos[:, None], m.rope_dim)  # [B, 1, rope/2]
    row = pos[:, None]
    q = _queries(cfg, p, x, sin, cos)[:, 0]  # [B, H, qk]
    new = latent(cfg, p, x, sin, cos).astype(cache.dtype)  # [B, 1, r + rope]
    wkv_b = p["wkv_b"].astype(cd).reshape(r, h, m.nope_dim + m.v_dim)
    # fold the key half of wkv_b into the query: scores against the latent
    q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :m.nope_dim], wkv_b[..., :m.nope_dim],
                       preferred_element_type=jnp.float32)
    qc = jnp.concatenate([q_lat, q[..., m.nope_dim:].astype(jnp.float32)], -1)
    qc = (qc * m.qk_dim ** -0.5).astype(cd)  # [B, H, r + rope]
    s_new = jnp.einsum("bhc,bc->bh", qc, new[:, 0],
                       preferred_element_type=jnp.float32)
    if kernel._on_tpu():
        acc, top_c, sum_c = kernel.latent_decode(qc, cache, pos, layer, r)
        # the token's own latent merged into the kernel's running softmax,
        # over its whole row and sliced after (sliced first, the row takes a
        # layout with the batch minor, and each row's write after the scan
        # copies it)
        top = jnp.maximum(top_c, s_new)
        corr, p_new = jnp.exp(top_c - top), jnp.exp(s_new - top)
        o_lat = corr[..., None] * acc + (
            p_new[..., None] * new.astype(jnp.float32))[..., :r]
        o_lat = o_lat / (corr * sum_c + p_new)[..., None]
    else:
        o_lat = _attend_reference(qc, cache[layer], new, s_new, row, r, cd)
    # fold the value half of wkv_b into the output
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(cd), wkv_b[..., m.nope_dim:],
                   preferred_element_type=jnp.float32)
    y = o.reshape(b, 1, h * m.v_dim).astype(cd) @ p["wo"].astype(cd)
    return y.astype(x.dtype), new


def _attend_reference(qc, cache, new, s_new, row, r: int, cd):
    """The normalised latent [B, H, r] of ``qc`` over the cache's rows
    below ``row`` and the token's own ``new``, in ``jax.numpy`` over all
    ``CAP`` rows."""
    s_cache = jnp.einsum("bhc,btc->bht", qc, cache,
                         preferred_element_type=jnp.float32)
    idx = jnp.arange(cache.shape[1])
    s_cache = jnp.where((idx[None, :] < row)[:, None], s_cache, NEG_INF)
    top = jnp.maximum(s_cache.max(-1), s_new)
    p_cache = jnp.exp(s_cache - top[..., None])
    p_new = jnp.exp(s_new - top)
    # over whole rows (a slice of the cache would be copied); the rope
    # key's part is dropped after
    o_lat = jnp.einsum("bht,btc->bhc", p_cache.astype(cd), cache,
                       preferred_element_type=jnp.float32)
    o_lat = o_lat + p_new[..., None] * new.astype(jnp.float32)
    return o_lat[..., :r] / (p_cache.sum(-1) + p_new)[..., None]
