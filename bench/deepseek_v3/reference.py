"""Plain float32 reference of the DeepSeek-V3 block (arXiv:2412.19437) as
Moonlight-16B-A3B configures it: latent attention without a query latent
(DeepSeek-V2, arXiv:2405.04434), leading dense SwiGLU layers, then MoE
layers with sigmoid routing over 64 experts, a selection-only bias, top-k
renormalisation, a routed scale and always-on shared experts.

Plain ``jax.numpy``, every matrix product at ``precision="highest"``; no
kernel, cache or batching. Attention expands the latent into every head's
key and value. Each expert runs over every token and is weighted by the
gate the token gives it (zero where it was not picked). It imports nothing
of the program: weights come from ``weights.py`` beside it, drawn again
from the seed one layer at a time.

Departures, each also the program's: random weights; rope in the
rotate-half layout (the checkpoint interleaves; with random weights the two
are the same model); one expert group (``n_group`` 1), so group selection
is the identity.

``mode="fp8"`` is the control: every matrix product takes operands rounded
to float8 e4m3, scaled per row of the activations and per column of the
weights.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench.deepseek_v3 import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, w, mode: str):
    """a [..., K] @ w [K, N] in float32 or from fp8 operands."""
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x [..., S, d]; rotate-half, angles pos * theta^(-i/(d/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg, lw, h, mode):
    """Latent attention over h [n, S, D], sequences from position 0."""
    m, heads = cfg.mla, cfg.n_heads
    n, s, _ = h.shape
    pos = jnp.arange(s)
    q = mm(h, lw["mixer/wq"], mode).reshape(n, s, heads, m.qk_dim)
    kv_a = mm(h, lw["mixer/wkv_a"], mode)
    c = rms_norm(kv_a[..., :m.kv_rank], lw["mixer/kv_norm"], cfg.norm_eps)
    k_rope = rope(kv_a[..., m.kv_rank:], pos, cfg.rope_theta)  # [n, S, rope]
    kv = mm(c, lw["mixer/wkv_b"], mode).reshape(n, s, heads, m.nope_dim + m.v_dim)
    q_rope = rope(q[..., m.nope_dim:].transpose(0, 2, 1, 3), pos, cfg.rope_theta)
    q = jnp.concatenate([q[..., :m.nope_dim].transpose(0, 2, 1, 3), q_rope], -1)
    k = jnp.concatenate([kv[..., :m.nope_dim].transpose(0, 2, 1, 3),
                         jnp.broadcast_to(k_rope[:, None], (n, heads, s, m.rope_dim))],
                        -1)
    v = kv[..., m.nope_dim:].transpose(0, 2, 1, 3)  # [n, H, S, v]

    def one(qkv):  # one sequence and head: the score block is [S, S]
        qh, kh, vh = qkv
        if mode == "fp8":
            qh, kh, vh = _fp8(qh, -1), _fp8(kh, -1), _fp8(vh, 0)
        logits = jnp.matmul(qh, kh.T, precision=HIGHEST) * m.qk_dim ** -0.5
        mask = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        if mode == "fp8":
            p = _fp8(p, -1)
        return jnp.matmul(p, vh, precision=HIGHEST)

    flat = lambda a: a.reshape((n * heads,) + a.shape[2:])
    o = jax.lax.map(one, (flat(q), flat(k), flat(v)))
    o = o.reshape(n, heads, s, m.v_dim).transpose(0, 2, 1, 3).reshape(n, s, -1)
    return mm(o, lw["mixer/wo"], mode)


def swiglu(h, w1, w3, w2, mode):
    return mm(jax.nn.silu(mm(h, w1, mode)) * mm(h, w3, mode), w2, mode)


def route(cfg, lw, h, mode):
    """(gates [T, E]: each token's weight on each expert, zero where not
    picked) for h [T, D]."""
    moe = cfg.moe
    scores = jax.nn.sigmoid(mm(h, lw["mlp/router"], mode))
    _, idx = jax.lax.top_k(scores + lw["mlp/router_bias"], moe.top_k)
    picked = jnp.take_along_axis(scores, idx, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * moe.routed_scale
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(picked)


def moe(cfg, lw, h, mode):
    n, s, d = h.shape
    t = h.reshape(n * s, d)
    gates = route(cfg, lw, t, mode)

    def expert(y, e):
        out = swiglu(t, lw["mlp/w1"][e], lw["mlp/w3"][e], lw["mlp/w2"][e], mode)
        return y + gates[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(t), jnp.arange(cfg.moe.n_experts))
    y = y + swiglu(t, lw["mlp/shared/w1"], lw["mlp/shared/w3"],
                   lw["mlp/shared/w2"], mode)
    return y.reshape(n, s, d)


def layer(cfg, lw: Dict[str, jax.Array], x, dense: bool, mode: str = "f32"):
    """One layer over x [n, S, D]."""
    x = x + attention(cfg, lw, rms_norm(x, lw["norm1"], cfg.norm_eps), mode)
    h = rms_norm(x, lw["norm2"], cfg.norm_eps)
    if dense:
        return x + swiglu(h, lw["mlp/w1"], lw["mlp/w3"], lw["mlp/w2"], mode)
    return x + moe(cfg, lw, h, mode)


def final_logits(cfg, top, x, mode: str = "f32"):
    return mm(rms_norm(x, top["norm_f"], cfg.norm_eps), top["head"], mode)


# ------------------------------------------------------------- from a seed

@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg, key, tokens, mode):
    x = W.top_leaves(cfg, key)["tok"][tokens]
    return _fp8(x, -1) if mode == "fp8" else x


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer_from_seed(cfg, key, i, x, dense: bool, mode):
    return layer(cfg, W.layer_leaves(cfg, key, i, dense), x, dense, mode)


def hidden_from_seed(cfg, seed: int, tokens: jax.Array, mode: str = "f32"):
    """Final hidden states [n, S, D] before the last norm, each layer drawn
    from ``seed`` alone."""
    key = W.seed_key(seed)
    x = _embed(cfg, key, tokens, mode)
    for i in range(cfg.n_layers):
        x = _layer_from_seed(cfg, key, jnp.int32(i), x,
                             cfg.mlp_kind(i) == "dense", mode)
    return x


@functools.partial(jax.jit, static_argnums=(0,))
def head_from_seed(cfg, key):
    top = W.top_leaves(cfg, key)
    return {"norm_f": top["norm_f"], "head": top["head"]}
