"""Operations and bytes that a DeepSeek-V3-block model needs, counted from
shapes and from the engine's MoE counters.

They count the work the algorithm needs, not what the program does: a
decode step reads the weights of the experts its live rows routed to (not
all of them), each live slot's latent rows up to its position, and the
routed pairs' operations; attention in latent form at decode, expanded per
head at prefill (causal, half the square). So no honest reading passes 100%.
"""
from __future__ import annotations

from typing import Iterable


def mla_params(cfg) -> int:
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    return (d * h * m.qk_dim + d * m.cache_dim + m.kv_rank
            + m.kv_rank * h * (m.nope_dim + m.v_dim) + h * m.v_dim * d)


def expert_params(cfg) -> int:
    """One routed expert (SwiGLU)."""
    return 3 * cfg.d_model * cfg.moe.d_expert


def moe_layers(cfg) -> int:
    return sum(cfg.mlp_kind(i) == "moe" for i in range(cfg.n_layers))


def shared_params(cfg) -> int:
    """What every token of an MoE layer reads beside its experts: the
    router, its selection bias and the shared experts."""
    e = cfg.moe.n_experts
    return cfg.d_model * e + e + cfg.moe.n_shared * expert_params(cfg)


def dense_params(cfg) -> int:
    """The weights every token reads, outside the routed experts and the
    embeddings: attention, the dense MLPs, the shared part of the MoE
    layers, the norms."""
    d = cfg.d_model
    dense_layers = cfg.n_layers - moe_layers(cfg)
    return (cfg.n_layers * (mla_params(cfg) + 2 * d)
            + dense_layers * 3 * d * cfg.d_ff
            + moe_layers(cfg) * shared_params(cfg))


def head_params(cfg) -> int:
    return cfg.d_model * cfg.vocab


def params(cfg) -> int:
    """Every parameter: the embedding, the stack with all experts, the
    final norm and the head."""
    return (cfg.vocab * cfg.d_model + dense_params(cfg)
            + moe_layers(cfg) * cfg.moe.n_experts * expert_params(cfg)
            + cfg.d_model + head_params(cfg))


def latent_bytes_per_position(cfg, dtype_bytes: int = 2) -> int:
    """The latent and the rope key of one position, all layers."""
    return cfg.n_layers * cfg.mla.cache_dim * dtype_bytes


def pair_flops(cfg) -> int:
    """One routed (token, expert) pair: three matrix products."""
    return 2 * expert_params(cfg)


def pair_bytes(cfg, dtype_bytes: int = 2) -> int:
    """One pair's activations into and out of the three products."""
    return 3 * (cfg.d_model + cfg.moe.d_expert) * dtype_bytes


def decode_attention_flops(cfg, keys: int) -> int:
    """One token against ``keys`` latent rows, all layers: scores over the
    latent and rope key, then the latent weighted."""
    h, m = cfg.n_heads, cfg.mla
    return cfg.n_layers * 2 * h * (m.cache_dim + m.kv_rank) * keys


def token_flops(cfg) -> int:
    """One token through the weights outside the routed experts, and the
    head."""
    return 2 * (dense_params(cfg) + head_params(cfg))


def prefill_flops(cfg, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: every routed pair (top-k per token
    and MoE layer), causal attention with keys and values expanded per
    head, the head at the last position."""
    h, m = cfg.n_heads, cfg.mla
    attn = (cfg.n_layers * 2 * h * (m.qk_dim + m.v_dim)
            * prompt * (prompt + 1) // 2)
    pairs = prompt * cfg.moe.top_k * moe_layers(cfg)
    return (2 * dense_params(cfg) * prompt + pairs * pair_flops(cfg) + attn
            + 2 * head_params(cfg))


def decode_step_least(cfg, positions: Iterable[int], touched: float,
                      peak_flops: float, peak_bytes_s: float,
                      dtype_bytes: int = 2) -> float:
    """Least seconds of one decode step over the live slots at
    ``positions``, whose rows routed to ``touched`` (layer, expert) pairs:
    the weights outside the experts and the touched experts read once, of
    the input embedding only the rows looked up, each live slot's latent
    rows up to its position; against the live rows' operations."""
    positions = list(positions)
    live = len(positions)
    weights = (dense_params(cfg) + head_params(cfg) + cfg.d_model
               + live * cfg.d_model + touched * expert_params(cfg)) * dtype_bytes
    latent = sum(p + 1 for p in positions) * latent_bytes_per_position(cfg, dtype_bytes)
    pairs = live * cfg.moe.top_k * moe_layers(cfg)
    flops = (live * token_flops(cfg) + pairs * pair_flops(cfg)
             + sum(decode_attention_flops(cfg, p + 1) for p in positions))
    return max((weights + latent) / peak_bytes_s, flops / peak_flops)


def gmm_least(cfg, pairs: float, touched: float, peak_flops: float,
              peak_bytes_s: float, dtype_bytes: int = 2) -> float:
    """Least seconds of the grouped matmuls over ``pairs`` routed pairs
    that touched ``touched`` (layer, expert) pairs: the touched experts'
    weights once and the pairs' activations in and out, against the pairs'
    operations."""
    b = (touched * expert_params(cfg) * dtype_bytes
         + pairs * pair_bytes(cfg, dtype_bytes))
    return max(b / peak_bytes_s, pairs * pair_flops(cfg) / peak_flops)
