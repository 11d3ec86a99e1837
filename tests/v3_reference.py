"""Plain float32 reference of the DeepSeek-V3 block (arXiv:2412.19437;
latent attention of DeepSeek-V2, arXiv:2405.04434) over the program's
parameter tree, for the tests: plain ``jax.numpy`` at ``precision=
"highest"``, with no kernel, cache or batching. Attention expands the
latent into every head's key and value; every expert runs over every token
and is weighted by the gate the token gives it (zero where not picked).
The benchmark keeps its own copy (``bench/deepseek_v3/reference.py``).
"""
import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, w):
    return jnp.matmul(a, w.astype(jnp.float32), precision=HI)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    ang = pos[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def layer_params(cfg, params, i):
    """Layer i's params, sliced out of the program's stack."""
    k = cfg.first_k_dense
    if i < k:
        entry, g = 0, i
    else:
        entry = (1 if k else 0) + (i - k) % cfg.period
        g = (i - k) // cfg.period
    return jax.tree.map(lambda a: a[g], params["layers"][entry])


def attention(cfg, p, h):
    m, H = cfg.mla, cfg.n_heads
    n, s, _ = h.shape
    pos = jnp.arange(s, dtype=jnp.float32)
    q = _mm(h, p["wq"]).reshape(n, s, H, m.qk_dim)
    kv_a = _mm(h, p["wkv_a"])
    c = _norm(kv_a[..., :m.kv_rank], p["kv_norm"]["w"], cfg.norm_eps)
    k_rope = _rope(kv_a[..., m.kv_rank:], pos, cfg.rope_theta)
    kv = _mm(c, p["wkv_b"]).reshape(n, s, H, m.nope_dim + m.v_dim)
    q = jnp.concatenate([q[..., :m.nope_dim],
                         _rope(q[..., m.nope_dim:].swapaxes(1, 2), pos,
                               cfg.rope_theta).swapaxes(1, 2)], -1)
    k = jnp.concatenate([kv[..., :m.nope_dim],
                         jnp.broadcast_to(k_rope[:, :, None],
                                          (n, s, H, m.rope_dim))], -1)
    logits = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) * m.qk_dim ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), -1)
    o = jnp.einsum("nhqk,nkhd->nqhd", w, kv[..., m.nope_dim:], precision=HI)
    return _mm(o.reshape(n, s, H * m.v_dim), p["wo"])


def swiglu(p, h):
    return _mm(jax.nn.silu(_mm(h, p["w1"])) * _mm(h, p["w3"]), p["w2"])


def gates(cfg, p, t):
    """[T, E]: each token's weight on each expert (zero where not picked):
    sigmoid scores, top-k of scores plus the selection bias, the picked
    scores renormalised and scaled."""
    moe = cfg.moe
    scores = jax.nn.sigmoid(_mm(t, p["router"]))
    _, idx = jax.lax.top_k(scores + p["router_bias"], moe.top_k)
    picked = jnp.take_along_axis(scores, idx, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * moe.routed_scale
    return jnp.zeros_like(scores).at[jnp.arange(t.shape[0])[:, None], idx].set(picked)


def moe(cfg, p, h):
    n, s, d = h.shape
    t = h.reshape(n * s, d)
    g = gates(cfg, p, t)
    y = swiglu(p["shared"], t)
    for e in range(cfg.moe.n_experts):
        y = y + g[:, e, None] * swiglu({w: p[w][e] for w in ("w1", "w2", "w3")}, t)
    return y.reshape(n, s, d)


def logits(cfg, params, tokens):
    """Logits [n, S, V] of the whole forward pass."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = params["embed"]["tok"][tokens]
    for i in range(cfg.n_layers):
        p = layer_params(cfg, params, i)
        x = x + attention(cfg, p["mixer"], _norm(x, p["norm1"]["w"], cfg.norm_eps))
        h = _norm(x, p["norm2"]["w"], cfg.norm_eps)
        x = x + (swiglu(p["mlp"], h) if cfg.mlp_kind(i) == "dense"
                 else moe(cfg, p["mlp"], h))
    return _mm(_norm(x, params["norm_f"]["w"], cfg.norm_eps), params["embed"]["head"])
