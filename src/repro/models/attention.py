"""GQA attention: train/prefill (flash on TPU, chunked-jnp elsewhere) and
single-token decode over a KV cache (flash-decode-style when the cache is
sequence-sharded).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.parallel import context as pctx

NEG_INF = -1e30


def init_attention(cfg, rng, cross: bool = False) -> Dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.jnp_param_dtype()
    ks = jax.random.split(rng, 4)
    sc = d ** -0.5
    p = {
        "wq": L.normal(ks[0], (d, h * dh), sc, dt),
        "wk": L.normal(ks[1], (d, hkv * dh), sc, dt),
        "wv": L.normal(ks[2], (d, hkv * dh), sc, dt),
        "wo": L.normal(ks[3], (h * dh, d), (h * dh) ** -0.5, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dt)
        p["bk"] = jnp.zeros((hkv * dh,), dt)
        p["bv"] = jnp.zeros((hkv * dh,), dt)
    return p


def _project_qkv(cfg, p, x, kv_x=None):
    """x [B,S,D] -> q [B,H,S,dh], k/v [B,Hkv,Skv,dh]."""
    cd = cfg.jnp_compute_dtype()
    b, s, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    skv = kv_x.shape[1]
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x.astype(cd) @ p["wq"].astype(cd)
    k = kv_x.astype(cd) @ p["wk"].astype(cd)
    v = kv_x.astype(cd) @ p["wv"].astype(cd)
    if "bq" in p:
        q, k, v = q + p["bq"].astype(cd), k + p["bk"].astype(cd), v + p["bv"].astype(cd)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, skv, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, skv, hkv, dh).transpose(0, 2, 1, 3)
    return q, k, v


def chunked_attention(
    q: jax.Array,  # [B,H,S,dh]
    k: jax.Array,  # [B,Hkv,Skv,dh]
    v: jax.Array,
    causal: bool,
    chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention scanned over KV chunks — the jnp mirror of the
    Pallas flash kernel, with O(S·chunk) peak memory. Scores/softmax run in
    f32; the two big einsums take bf16 operands with f32 accumulation, so the
    dominant transient is one [.., S, chunk] f32 score block."""
    b, h, s, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = h // hkv
    cd = q.dtype
    qg = (q.astype(jnp.float32) * (dh ** -0.5)).astype(cd).reshape(
        b, hkv, g, s, dh
    )
    c = min(chunk, skv)
    while skv % c:
        c //= 2
    nc = skv // c
    kc = k.reshape(b, hkv, nc, c, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, c, dh).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(s)

    @jax.checkpoint  # drop per-chunk score residuals (recompute in bwd)
    def body(carry, inp):
        m, l, acc, ci = carry
        ki, vi = inp
        s_ij = jnp.einsum("bhgqd,bhkd->bhgqk", qg, ki,
                          preferred_element_type=jnp.float32)
        if causal:
            k_pos = ci * c + jnp.arange(c)
            mask = q_pos[:, None] >= k_pos[None, :]
            s_ij = jnp.where(mask[None, None, None], s_ij, NEG_INF)
        m_new = jnp.maximum(m, s_ij.max(-1))
        p_ij = jnp.exp(s_ij - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p_ij.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p_ij.astype(cd), vi,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new, ci + 1), None

    m0 = jnp.full((b, hkv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, s, dh), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.zeros((), jnp.int32)),
                                     (kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, s, dh).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool, chunk: int) -> jax.Array:
    """``kops.attention`` (the Pallas flash kernel on a TPU), differentiable
    and run per shard under a mesh. The kernel has no backward pass, so its
    gradient is that of ``chunked_attention``, the same function in jnp,
    recomputed from q/k/v."""
    return pctx.per_shard_attention(
        functools.partial(kops.attention, causal=causal), q, k, v)


def _flash_fwd(q, k, v, causal, chunk):
    return flash_attention(q, k, v, causal, chunk), (q, k, v)


def _flash_bwd(causal, chunk, residuals, g):
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(q, k, v, causal=causal, chunk=chunk),
        *residuals)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention_forward(
    cfg,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    causal: bool = True,
    kv_x: Optional[jax.Array] = None,
    use_rope: bool = True,
    chunked_threshold: int = 4096,
) -> jax.Array:
    """Self (or cross, via kv_x) attention for train/prefill. Returns
    (output [B,S,D], (k, v) for cache)."""
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    if use_rope and kv_x is None:
        sin, cos = L.rope_tables(cfg, positions)  # [S, dh/2] — broadcasts
        q = L.apply_rope(q, sin, cos)
        k = L.apply_rope(k, sin, cos)
    s = q.shape[2]
    if kv_x is not None:
        out = chunked_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    elif s >= chunked_threshold:
        out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    else:
        out = flash_attention(q, k, v, causal, cfg.attn_chunk)
    b = x.shape[0]
    cd = cfg.jnp_compute_dtype()
    merged = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = merged.astype(cd) @ p["wo"].astype(cd)
    return y.astype(x.dtype), (k, v)


def decode_attention(
    cfg,
    p: Dict,
    x: jax.Array,  # [B, 1, D]
    cache_k: jax.Array,  # [B, Hkv, CAP, dh]
    cache_v: jax.Array,
    pos: jax.Array,  # [B] i32, each row's own depth
    cross: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """One-token attention over the cache; returns (y, k_new, v_new).

    ``pos`` [B] is the index where each row's new token sits: RoPE angles
    and the validity mask are per row, so rows at different sequence
    lengths share one decode launch.

    The cache is only read: the token attends over the cache's positions
    ``< pos`` and over its own key and value, which stand in for the cache
    row at ``pos``. ``k_new``/``v_new`` ([B, Hkv, 1, dh], in the cache's
    dtype) are returned for the caller to write at ``pos``
    (``transformer.write_decode_cache``), once for every layer, in place.
    The result equals that of writing first and attending over ``<= pos``.

    For cross-attention the cache is the (static) encoder projection and
    ``k_new``/``v_new`` are None. The einsums reduce over the cache's
    sequence axis — when that axis is sharded (long-context SP), XLA turns
    the reductions into partial sums + all-reduce: a flash-decode combine."""
    cd = cfg.jnp_compute_dtype()
    b = x.shape[0]
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    cap = cache_k.shape[2]
    idx = jnp.arange(cap)

    q = (x.astype(cd) @ p["wq"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
    q = q.reshape(b, h, dh)  # S=1 folded away

    knew = vnew = None
    keys, values = cache_k, cache_v
    if cross:
        valid = jnp.ones((b, 1, 1, cap), bool)
    else:
        knew = (x.astype(cd) @ p["wk"].astype(cd))
        vnew = (x.astype(cd) @ p["wv"].astype(cd))
        if "bk" in p:
            knew, vnew = knew + p["bk"].astype(cd), vnew + p["bv"].astype(cd)
        knew = knew.reshape(b, hkv, 1, dh)
        vnew = vnew.reshape(b, hkv, 1, dh).astype(cache_v.dtype)
        # per-row tables [B, 1, dh/2]; lift to [B, 1, 1, dh/2] so they
        # broadcast over the head axis of q [B, H, 1, dh] / knew
        sin, cos = L.rope_tables(cfg, pos[:, None].astype(jnp.int32))
        sin, cos = sin[:, None], cos[:, None]
        row = pos[:, None]  # [B, 1]: each row's own position
        q = L.apply_rope(q.reshape(b, h, 1, dh), sin, cos).reshape(b, h, dh)
        knew = L.apply_rope(knew, sin, cos).astype(cache_k.dtype)
        # the new token in place of the cache row at pos, selected inside
        # the read: no copy of the cache is made
        at = (idx[None, :] == row)[:, None, :, None]  # [B, 1, cap, 1]
        keys = jnp.where(at, knew, cache_k)
        values = jnp.where(at, vnew, cache_v)
        valid = (idx[None, :] <= row)[:, None, None]  # [B, 1, 1, cap]

    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32) * (dh ** -0.5)
    logits = jnp.einsum("bhgd,bhkd->bhgk", qg, keys.astype(jnp.float32))
    logits = jnp.where(valid, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", w, values.astype(jnp.float32))
    merged = out.reshape(b, 1, h * dh).astype(cd)
    y = merged @ p["wo"].astype(cd)
    return y.astype(x.dtype), knew, vnew
