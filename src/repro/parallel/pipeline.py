"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

At 2+ pods, inter-pod ICI is the scarcest link — pipelining the layer stack
across pods exchanges only the [micro_batch, seq, d_model] activations at
stage boundaries (vs FSDP's per-layer weight gathers crossing pods).

Implementation: ``shard_map`` over the ``pod`` axis; each pod holds its
stage's parameter slice (leading stage dim sharded over ``pod``), and a
``lax.scan`` over ``n_micro + n_stages - 1`` clock ticks runs the classic
GPipe schedule: at tick t, stage s processes microbatch ``t - s`` (bubble
ticks compute-and-discard); activations move stage→stage+1 with
``jax.lax.ppermute``. The returned structure composes with the rest of the
framework (the stage function is any ``f(stage_params, x) -> x``).

This is the forward pipeline (inference / activation-forward for PP+grad
via jax.grad — scan+ppermute are differentiable, giving the standard GPipe
fill/drain backward automatically). Tested for numeric equivalence against
sequential execution on a (pod=2, data, model) mini-mesh
(tests/test_pipeline.py).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,  # [n_micro, micro_batch, ...] microbatched input
    *,
    mesh,
    axis: str = "pod",
    param_specs=None,
    x_spec: P = None,
) -> jax.Array:
    """Run ``stage_fn`` as a pipeline over ``axis``. ``stage_params`` leaves
    must have a leading stage dim equal to the axis size; ``x`` is
    microbatched on its leading dim. Returns outputs with x's structure."""
    n_stages = int(mesh.shape[axis])
    n_micro = x.shape[0]
    assert n_micro >= 1
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    if x_spec is None:
        x_spec = P()  # microbatches replicated across the pipeline axis

    def staged(params, xs):
        # inside shard_map: params leaves have leading dim 1 (this stage)
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 ingests microbatch t (when valid); others take the
            # activation permuted in from the previous stage at tick end
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            x_in = jnp.where(stage == 0, feed, inflight)
            y = stage_fn(params, x_in)
            # last stage commits microbatch (t - n_stages + 1) when valid
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outputs), None

        zeros = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (zeros, outs0), jnp.arange(ticks)
        )
        # only the last stage holds real outputs; replicate across the
        # pipeline axis (masked psum = broadcast from the last stage)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    mapped = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        axis_names={axis},
        check_vma=False,
    )
    return mapped(stage_params, x)
