"""Fault-tolerant training driver.

Single entry point for real runs and CPU-scale examples:

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Features exercised regardless of scale: deterministic resumable data,
async atomic checkpointing + keep-k GC, failure injection + bounded
restarts (restore from latest), straggler monitoring, heartbeats, optional
mesh + sharded state, grad accumulation, int8 grad compression.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import os
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.configs.base import get_config
from repro.checkpoint import store
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import SyntheticConfig, SyntheticTokens
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import steps as steps_mod
from repro.models.model import Model
from repro.optim import adamw
from repro.parallel import context as pctx
from repro.parallel import sharding as sh
from repro.runtime.failure import FailureInjector, InjectedFailure, RestartPolicy
from repro.runtime.straggler import Heartbeat, StragglerMonitor


@dataclasses.dataclass
class TrainOptions:
    steps: int = 50
    batch: int = 8
    seq: int = 128
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 20
    keep: int = 3
    accum_steps: int = 1
    grad_compression: Optional[str] = None
    state_dtype: str = "float32"
    lr: float = 3e-4
    seed: int = 0
    mesh_shape: Optional[tuple] = None  # e.g. (2, 4) -> ('data','model')
    log_every: int = 10


def build_state(model: Model, opt_cfg: adamw.AdamWConfig, seed: int, mesh=None):
    """Parameters and optimizer state. On a mesh every leaf is made by a
    jitted program whose outputs are already sharded, so no leaf is ever
    whole on one device."""
    key = jax.random.key(seed)
    init_opt = functools.partial(adamw.init_state, opt_cfg)
    if mesh is None:
        params = model.init(key)
        return params, init_opt(params)
    p_sh = sh.params_sharding(jax.eval_shape(model.init, key), mesh)
    params = jax.jit(model.init, out_shardings=p_sh)(key)
    o_sh = sh.opt_state_sharding(jax.eval_shape(init_opt, params), params, mesh)
    return params, jax.jit(init_opt, out_shardings=o_sh)(params)


@contextlib.contextmanager
def _mesh_context(mesh_shape):
    """The run's mesh (None without one), current and with the activation
    sharding rules installed for as long as the run lasts."""
    if not mesh_shape:
        yield None
        return
    mesh = mesh_mod.make_mesh(mesh_shape, ("data", "model"))
    pctx.install(("data",), tp_size=int(mesh.shape["model"]), sp_seq=False)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        pctx.clear()


def train(cfg, opts: TrainOptions, injector: Optional[FailureInjector] = None,
          monitor: Optional[StragglerMonitor] = None) -> Dict[str, Any]:
    with _mesh_context(opts.mesh_shape) as mesh:
        return _train(cfg, opts, mesh, injector, monitor)


def _train(cfg, opts: TrainOptions, mesh, injector, monitor) -> Dict[str, Any]:
    model = Model(cfg)
    opt_cfg = adamw.AdamWConfig(lr=opts.lr, state_dtype=opts.state_dtype)
    params, opt_state = build_state(model, opt_cfg, opts.seed, mesh)
    p_sh = sh.params_sharding(params, mesh) if mesh is not None else None
    step_fn = steps_mod.make_train_step(
        model, opt_cfg, accum_steps=opts.accum_steps,
        grad_compression=opts.grad_compression, grad_shardings=p_sh,
    )
    jit_kwargs = {}
    if mesh is not None:
        batch_abstract = {
            "tokens": jax.ShapeDtypeStruct((opts.batch, opts.seq), np.int32),
            "labels": jax.ShapeDtypeStruct((opts.batch, opts.seq), np.int32),
        }
        o_sh = sh.opt_state_sharding(opt_state, params, mesh)
        jit_kwargs = dict(
            in_shardings=(p_sh, o_sh, sh.batch_sharding(batch_abstract, mesh)),
            out_shardings=(p_sh, o_sh, None),
        )
    jitted = jax.jit(step_fn, **jit_kwargs)

    start_step = 0
    ckpt = None
    if opts.ckpt_dir:
        ckpt = store.AsyncCheckpointer(opts.ckpt_dir, keep=opts.keep)
        latest = store.latest_step(opts.ckpt_dir)
        if latest is not None:
            (params, opt_state), manifest = store.restore(
                opts.ckpt_dir, (params, opt_state), step=latest
            )
            start_step = latest
            print(f"[train] resumed from step {start_step}")

    source = SyntheticTokens(
        SyntheticConfig(cfg.vocab, opts.seq, opts.batch, seed=opts.seed)
    )
    loader = PrefetchLoader(source, start_step=start_step)
    monitor = monitor or StragglerMonitor()
    hb = Heartbeat(os.path.join(opts.ckpt_dir, "HEARTBEAT")) if opts.ckpt_dir \
        else None

    history = []
    step = start_step
    try:
        while step < opts.steps:
            t0 = time.perf_counter()
            _, np_batch = loader.get(step)
            batch = {k: jax.numpy.asarray(v) for k, v in np_batch.items()}
            if injector:
                injector.maybe_fail(step, "step")
            params, opt_state, metrics = jitted(params, opt_state, batch)
            dt = time.perf_counter() - t0
            ev = monitor.record(step, dt, loader.fetch_seconds.get(step, 0.0))
            if ev:
                print(f"[straggler] step {step}: {ev.mitigation} "
                      f"({ev.step_seconds:.2f}s vs median {ev.median_seconds:.2f}s)")
            if hb:
                hb.beat(step)
            step += 1
            if step % opts.log_every == 0 or step == opts.steps:
                loss = float(metrics["loss"])
                history.append((step, loss, dt))
                print(f"[train] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
            if ckpt and (step % opts.ckpt_every == 0 or step == opts.steps):
                if injector:
                    injector.maybe_fail(step, "save")
                ckpt.save(step, (params, opt_state), meta={"loss": float(
                    metrics["loss"])})
    finally:
        loader.close()
        if ckpt:
            ckpt.wait()
    return {"params": params, "opt_state": opt_state, "history": history,
            "final_step": step}


def train_with_recovery(cfg, opts: TrainOptions,
                        injector: Optional[FailureInjector] = None,
                        policy: Optional[RestartPolicy] = None) -> Dict[str, Any]:
    """Outer supervision loop: on failure, restart from latest checkpoint."""
    policy = policy or RestartPolicy()
    while True:
        try:
            return train(cfg, opts, injector=injector)
        except InjectedFailure as e:  # noqa: PERF203
            print(f"[recovery] {e}; restarting "
                  f"({policy.restarts + 1}/{policy.max_restarts})")
            if not policy.should_restart(e):
                raise


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compression", choices=["int8"], default=None)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 (needs XLA_FLAGS host devices)")
    ap.add_argument("--schedule-db", default=None,
                    help="warm repro.tuna schedule DB (JSONL); kernel "
                         "block-spec picks become pure lookups")
    args = ap.parse_args()

    if args.schedule_db:
        from repro.kernels.ops import use_schedule_db

        use_schedule_db(args.schedule_db)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opts = TrainOptions(
        steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        accum_steps=args.accum, lr=args.lr,
        grad_compression=args.grad_compression,
        mesh_shape=tuple(int(x) for x in args.mesh.split("x")) if args.mesh
        else None,
    )
    out = train_with_recovery(cfg, opts)
    print(f"done at step {out['final_step']}; "
          f"last loss {out['history'][-1][1] if out['history'] else float('nan'):.4f}")


if __name__ == "__main__":
    main()
