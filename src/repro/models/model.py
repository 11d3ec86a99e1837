"""Model facade: init / loss / prefill / decode for every arch family.

Batch schemas (all arrays device-shardable):
  LM families:  {"tokens": [B,S] i32, "labels": [B,S] i32}
  audio:        + {"frames": [B, n_frontend_tokens, D]}     (STUB frontend)
  vlm:          + {"patches": [B, n_frontend_tokens, D]}    (STUB frontend)

Decode state (``DecodeState``) carries the per-layer cache tuple, the
position (scalar for lockstep waves, ``[B]`` for per-slot continuous
batching), and (enc-dec only) cross-attention caches built at prefill.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel import context as pctx


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg, rng) -> Dict:
    """Parameters of ``cfg`` drawn from ``rng`` by one compiled program. Each
    leaf is drawn and cast to the parameter dtype inside it, so at full
    width no f32 copy of a stacked leaf reaches device memory, and a caller
    can place the leaves where they belong (``out_shardings``) as they are
    made."""
    ks = jax.random.split(rng, 4)
    params: Dict[str, Any] = {
        "embed": L.init_embed(cfg, ks[0]),
        "norm_f": L.init_norm(cfg),
        "layers": T.init_stack(cfg, ks[1], cross=cfg.encoder_decoder),
    }
    if cfg.encoder_decoder:
        enc_pattern = (("attention", "dense"),)
        params["encoder"] = T.init_stack(
            cfg, ks[2], n_layers=cfg.n_encoder_layers, pattern=enc_pattern
        )
        params["enc_norm_f"] = L.init_norm(cfg)
        params["enc_pos"] = L.normal(
            ks[3], (cfg.n_frontend_tokens, cfg.d_model), 0.02,
            cfg.jnp_param_dtype(),
        )
    if cfg.frontend == "vision":
        params["vis_proj"] = L.normal(
            ks[3], (cfg.d_model, cfg.d_model), cfg.d_model ** -0.5,
            cfg.jnp_param_dtype(),
        )
    return params


@dataclasses.dataclass
class Model:
    cfg: Any

    # ------------------------------------------------------------------ init
    def init(self, rng) -> Dict:
        return init_params(self.cfg, rng)

    # --------------------------------------------------------------- helpers
    def _encode(self, params, frames):
        """Whisper encoder over stub frame embeddings [B, T, D]."""
        cfg = self.cfg
        x = frames.astype(cfg.jnp_compute_dtype()) + params["enc_pos"].astype(
            cfg.jnp_compute_dtype()
        )
        pos = jnp.arange(frames.shape[1])
        x, _, _ = T.apply_stack(cfg, params["encoder"], x, pos, causal=False,
                                pattern=(("attention", "dense"),))
        return L.apply_norm(cfg, params["enc_norm_f"], x)

    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array, int]:
        """Returns (x [B, S_total, D], positions, n_prefix)."""
        cfg = self.cfg
        x = L.embed(cfg, params["embed"], batch["tokens"])
        n_prefix = 0
        if cfg.frontend == "vision":
            cd = cfg.jnp_compute_dtype()
            patches = batch["patches"].astype(cd) @ params["vis_proj"].astype(cd)
            x = jnp.concatenate([patches, x], axis=1)
            n_prefix = patches.shape[1]
        pos = jnp.arange(x.shape[1])
        return pctx.constrain_tokens(x), pos, n_prefix

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        enc_out = None
        if cfg.encoder_decoder:
            enc_out = self._encode(params, batch["frames"])
        x, pos, n_prefix = self._embed_inputs(params, batch)
        x, _, aux = T.apply_stack(cfg, params["layers"], x, pos, causal=True,
                                  enc_out=enc_out)
        x = L.apply_norm(cfg, params["norm_f"], x)
        if n_prefix:
            x = x[:, n_prefix:]
        ce = L.cross_entropy_loss(cfg, params["embed"], x, batch["labels"])
        total = ce
        metrics = {"ce": ce}
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_weight * aux
            metrics["aux"] = aux
        metrics["loss"] = total
        return total, metrics

    def forward_hidden(self, params, batch) -> jax.Array:
        """Final hidden states (used by tests)."""
        cfg = self.cfg
        enc_out = self._encode(params, batch["frames"]) if cfg.encoder_decoder else None
        x, pos, _ = self._embed_inputs(params, batch)
        x, _, _ = T.apply_stack(cfg, params["layers"], x, pos, causal=True,
                                enc_out=enc_out)
        return L.apply_norm(cfg, params["norm_f"], x)

    def logits(self, params, batch) -> jax.Array:
        x = self.forward_hidden(params, batch)
        n_prefix = self.cfg.n_frontend_tokens if self.cfg.frontend == "vision" else 0
        if n_prefix:
            x = x[:, n_prefix:]
        return L.unembed(self.cfg, params["embed"], x)

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, cap: int) -> Tuple:
        cfg = self.cfg
        cross_len = cfg.n_frontend_tokens if cfg.encoder_decoder else 0
        return T.init_stack_cache(cfg, batch, cap, cross_len=cross_len)

    def prefill(self, params, batch, cap: int):
        """Run the prompt, build a decode cache of capacity ``cap``.
        Returns (cache, pos_next, last_logits)."""
        cfg = self.cfg
        enc_out = self._encode(params, batch["frames"]) if cfg.encoder_decoder else None
        x, pos, n_prefix = self._embed_inputs(params, batch)
        s_total = x.shape[1]
        assert cap >= s_total, (cap, s_total)
        x, caches, _ = T.apply_stack(cfg, params["layers"], x, pos, causal=True,
                                     enc_out=enc_out, collect_cache=True)
        x = L.apply_norm(cfg, params["norm_f"], x)

        def pad_cache(leaf):
            # attention k/v: [G, B, Hkv, S, dh] -> capacity cap on axis 3
            if leaf.ndim == 5 and leaf.shape[3] == s_total:
                pad = [(0, 0)] * 5
                pad[3] = (0, cap - s_total)
                return jnp.pad(leaf, pad)
            return leaf

        caches = jax.tree.map(pad_cache, caches)
        last_logits = L.unembed(cfg, params["embed"], x[:, -1:])
        return caches, jnp.asarray(s_total, jnp.int32), last_logits

    def decode_step(self, params, cache, token: jax.Array, pos: jax.Array):
        """token [B] i32; pos scalar i32 (all rows at the same depth) or
        [B] i32 (per-slot depths — continuous batching, each row attends,
        ropes and cache-writes at its own position).
        Returns (logits [B, V], new_cache)."""
        cfg = self.cfg
        x = L.embed(cfg, params["embed"], token[:, None])
        x, new_cache = T.apply_stack_decode(cfg, params["layers"], x, cache, pos)
        x = L.apply_norm(cfg, params["norm_f"], x)
        logits = L.unembed(cfg, params["embed"], x)[:, 0]
        return logits, new_cache
