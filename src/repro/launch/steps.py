"""The training step (what gets jit-ed, lowered, and dry-run compiled)."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine
from repro.parallel import collectives


def make_train_step(
    model: Model,
    opt_cfg: adamw.AdamWConfig,
    *,
    accum_steps: int = 1,
    grad_compression: Optional[str] = None,  # None | "int8"
    schedule: Callable = warmup_cosine,
    grad_shardings=None,  # pytree of NamedSharding matching params
    grad_dtype=None,  # accumulate/reduce grads in this dtype (e.g. bf16)
) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    With ``accum_steps > 1`` the batch's leading dim is split into
    microbatches scanned sequentially (grad accumulation); pass
    ``grad_shardings`` so the f32 accumulator is sharded like the params
    (left to propagation XLA replicates it — 24 GiB/device at 6B scale).
    Optional int8 gradient compression quantises grads before the data-
    parallel reduction — see parallel/collectives.py.
    """

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def _constrain(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_shardings)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            def micro(carry, mb):
                gsum, msum = carry
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                if grad_dtype is not None:
                    g = jax.tree.map(lambda x: x.astype(grad_dtype), g)
                gsum = _constrain(jax.tree.map(jnp.add, gsum, g))
                msum = jax.tree.map(jnp.add, msum, m)
                return (gsum, msum), None

            mbs = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]),
                batch,
            )
            acc_dt = grad_dtype or jnp.float32
            zeros_g = _constrain(jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params
            ))
            zeros_m = {"ce": jnp.zeros((), jnp.float32),
                       "loss": jnp.zeros((), jnp.float32)}
            if model.cfg.moe is not None:
                zeros_m["aux"] = jnp.zeros((), jnp.float32)
            (grads, msum), _ = jax.lax.scan(micro, (zeros_g, zeros_m), mbs)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            metrics = jax.tree.map(lambda m: m / accum_steps, msum)

        if grad_compression == "int8":
            grads = collectives.int8_compress_decompress(grads)

        lr_scale = schedule(opt_state["step"])
        params, opt_state, om = adamw.apply_updates(
            opt_cfg, params, grads, opt_state, lr_scale=lr_scale
        )
        metrics = dict(metrics)
        metrics.update(om)
        return params, opt_state, metrics

    return train_step
