"""Mixture-of-Experts MLP: routing, a dropless grouped expert layer and the
shared experts.

Routing scores every expert (softmax, or DeepSeek-V3's sigmoid), picks the
top k (by score, or by score plus a per-expert bias that only selects,
``noaux_tc``), renormalises the picked scores and scales them by
``routed_scale``.

Dropless: every routed (token, expert) pair is computed. The pairs are
sorted by expert and each expert's group is padded to a multiple of the
row tile, so one grouped matmul (``kernels/moe_gmm.py``) computes them all,
reading each touched expert's weights once; padding is less than one tile
per expert and no pair is ever dropped.

Aux loss: Switch load-balancing  E · Σ_e f_e · P_e.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import moe_gmm
from repro.models import layers as L

# counters of a decode step, summed over layers (``apply_moe``'s ``stats``):
# routed pairs, (layer, expert) pairs with at least one token, and the
# busiest expert's tokens
N_STATS = 3


def init_moe(cfg, rng) -> Dict:
    moe = cfg.moe
    d, fe, e = cfg.d_model, moe.d_expert, moe.n_experts
    dt = cfg.jnp_param_dtype()
    ks = jax.random.split(rng, 5)
    sc_in, sc_out = d ** -0.5, fe ** -0.5
    p = {
        "router": L.normal(ks[0], (d, e), sc_in, dt),
        "w1": L.normal(ks[1], (e, d, fe), sc_in, dt),
        "w2": L.normal(ks[2], (e, fe, d), sc_out, dt),
    }
    if cfg.activation in ("swiglu", "geglu"):
        p["w3"] = L.normal(ks[3], (e, d, fe), sc_in, dt)
    if moe.select_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if moe.n_shared:
        p["shared"] = L.init_dense_mlp(cfg, ks[4], d_ff=moe.n_shared * fe)
    return p


def route(cfg, p: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [..., D] -> (topk_idx [..., k], gates [..., k] f32, aux_loss)."""
    moe = cfg.moe
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # [..., E]
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"].astype(jnp.float32) if moe.select_bias \
        else scores
    _, idx = jax.lax.top_k(choice, moe.top_k)
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * moe.routed_scale
    # Switch aux loss: fraction of tokens per expert x mean router prob
    e = moe.n_experts
    lead = tuple(range(idx.ndim - 1))
    f = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32).mean(axis=lead)
    aux = e * jnp.sum(f * probs.mean(axis=lead))
    return idx, gates, aux


class Plan(NamedTuple):
    """Where each routed pair sits in the grouped matmul's rows."""
    row: jax.Array          # [P] row of pair p (pairs in token-major order)
    tile_expert: jax.Array  # [R // tm] expert of each row tile
    active: jax.Array       # [1] row tiles that hold pairs
    tm: int
    rows: int               # R


def row_tile(pairs: int, n_experts: int) -> int:
    """Row tile of the grouped matmul: about one tile per expert's share of
    the pairs, between 16 rows (a bf16 sublane tile) and 128."""
    t = 16
    while t < 128 and t * n_experts < pairs:
        t *= 2
    return t


def plan(flat_e: jax.Array, n_experts: int) -> Plan:
    """The grouped layout of the pairs routed to experts ``flat_e`` [P]:
    sorted by expert (stable), each expert's group padded to a multiple of
    the row tile."""
    p = flat_e.shape[0]
    tm = row_tile(p, n_experts)
    rows = -(-(p + min(n_experts, p) * (tm - 1)) // tm) * tm
    sizes = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)
    padded = (sizes + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    first = jnp.cumsum(sizes) - sizes  # first sorted pair of each expert
    row_sorted = (pad_end - padded)[e_sorted] + jnp.arange(p) - first[e_sorted]
    row = jnp.zeros((p,), jnp.int32).at[order].set(row_sorted.astype(jnp.int32))
    active = pad_end[-1] // tm
    tile_start = jnp.minimum(jnp.arange(rows // tm), jnp.maximum(active - 1, 0)) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(pad_end, tile_start, side="right"), n_experts - 1)
    return Plan(row, tile_expert.astype(jnp.int32),
                active.astype(jnp.int32)[None], tm, rows)


def experts(cfg, p: Dict, x: jax.Array, idx: jax.Array, gates: jax.Array):
    """The routed experts' output for tokens x [T, D] routed to ``idx``
    [T, k] with ``gates`` [T, k]: Σ_j gates[t, j] · expert_idx[t, j](x[t]),
    every pair computed. With ``p["layer"]`` the expert weights are the
    whole stack [G, E, ...] and the layer's index."""
    t, d = x.shape
    k = idx.shape[1]
    cd = cfg.jnp_compute_dtype()
    pl_ = plan(idx.reshape(t * k).astype(jnp.int32), cfg.moe.n_experts)
    # each row's token (0 on padding rows, whose input is zeroed)
    token = jnp.zeros((pl_.rows,), jnp.int32).at[pl_.row].set(
        jnp.arange(t * k, dtype=jnp.int32) // k)
    used = jnp.zeros((pl_.rows,), bool).at[pl_.row].set(True)
    xs = jnp.where(used[:, None], x.astype(cd)[token], 0)

    def mm(a, w):
        return moe_gmm.gmm(a, w.astype(cd), pl_.tile_expert, pl_.active,
                           pl_.tm, p.get("layer"))

    h = mm(xs, p["w1"])
    h = L._act(cfg, h, mm(xs, p["w3"]) if "w3" in p else None).astype(cd)
    out = mm(h, p["w2"])  # [R, D]
    pairs = out[pl_.row].reshape(t, k, d).astype(jnp.float32)
    return jnp.einsum("tkd,tk->td", pairs, gates.astype(jnp.float32))


def stats(idx: jax.Array, n_experts: int, live: Optional[jax.Array]):
    """[routed pairs, experts with at least one pair, the busiest expert's
    pairs] of tokens ``idx`` [T, k], counting only rows where ``live``."""
    w = jnp.ones(idx.shape, jnp.int32) if live is None else \
        jnp.broadcast_to(live.reshape(-1, 1), idx.shape).astype(jnp.int32)
    load = jnp.bincount(idx.reshape(-1), weights=w.reshape(-1),
                        length=n_experts).astype(jnp.int32)
    return jnp.stack([load.sum(), (load > 0).sum(dtype=jnp.int32), load.max()])


def apply_moe(cfg, p: Dict, x: jax.Array, live: Optional[jax.Array] = None):
    """x [B, S, D] -> (y [B, S, D], aux_loss, stats [N_STATS] int32); ``live``
    [B] marks the rows the counters count (all when None)."""
    b, s, d = x.shape
    idx, gates, aux = route(cfg, p, x)
    y = experts(cfg, p, x.reshape(b * s, d), idx.reshape(b * s, -1),
                gates.reshape(b * s, -1)).reshape(b, s, d)
    if cfg.moe.n_shared:
        y = y + L.apply_dense_mlp(cfg, p["shared"], x).astype(jnp.float32)
    rows = None if live is None else jnp.repeat(live, s)
    st = stats(idx.reshape(b * s, -1), cfg.moe.n_experts, rows)
    return y.astype(x.dtype), aux.astype(jnp.float32), st
