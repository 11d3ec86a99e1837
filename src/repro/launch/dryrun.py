"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be imported/run before any other jax usage — the first two lines pin
512 placeholder host devices so ``jax.make_mesh`` can build the production
meshes (jax locks the device count at first init).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
Outputs one JSON record per cell under experiments/dryrun/.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ARCH_IDS, get_config  # noqa: E402
from repro.core.hlo_features import loop_scaled_collectives, parse_collectives  # noqa: E402
from repro.launch import mesh as mesh_mod  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import context as pctx  # noqa: E402
from repro.parallel import sharding as sh  # noqa: E402


def pick_accum_steps(batch: int, seq: int, mesh, target_tokens: int = 4096) -> int:
    """Gradient-accumulation microbatching: bound per-device microbatch
    tokens so scan-boundary activations fit HBM (EXPERIMENTS §Dry-run)."""
    dp = mesh_mod.axis_size(mesh, mesh_mod.dp_axes(mesh))
    accum = 1
    while (
        (batch // accum) * seq // dp > target_tokens
        and batch % (accum * 2) == 0
        and (batch // (accum * 2)) % dp == 0
    ):
        accum *= 2
    return accum


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mesh=None,
    cfg=None,
    verbose: bool = True,
    variant: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Lower + compile one cell; returns the §Dry-run record.

    ``variant`` overrides distribution knobs for the §Perf hillclimb loop:
      accum_steps, grad_compression ("int8"), sp_seq (bool),
      state_dtype ("float32"|"bfloat16"|"int8"), remat (bool).
    """
    variant = variant or {}
    cfg = cfg or get_config(arch)
    cfg_over = {k: variant[k] for k in ("attn_chunk", "ssm_chunk",
                                        "mlstm_chunk", "remat_stack")
                if k in variant}
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    mesh = mesh if mesh is not None else mesh_mod.make_production_mesh(
        multi_pod=multi_pod
    )
    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, (int(mesh.shape[a]) for a in
                                           mesh.axis_names))),
        "n_devices": int(mesh.size),
    }
    ok, why = S.shape_applicable(cfg, shape_name)
    if not ok:
        record["status"] = "skipped"
        record["why"] = why
        return record

    spec = S.SHAPES[shape_name]
    kind, seq, batch = spec["kind"], spec["seq"], spec["batch"]
    model = Model(cfg)
    t0 = time.perf_counter()

    # SP for the token-parallel kinds (train/prefill); decode runs S=1
    pctx.install(
        mesh_mod.dp_axes(mesh),
        tp_axis="model",
        tp_size=int(mesh.shape["model"]),
        sp_seq=variant.get("sp_seq", kind in ("train", "prefill")),
        mesh=mesh if variant.get("mixer_shard_map", False) else None,
        moe_pin=variant.get("moe_pin", False),
    )
    with mesh:
        params_s = S.abstract_params(model)
        p_shard = sh.params_sharding(params_s, mesh, cfg=cfg)
        if kind == "train":
            state_dtype = variant.get(
                "state_dtype", S.recommended_state_dtype(cfg)
            )
            record["opt_state_dtype"] = state_dtype
            opt_cfg = adamw.AdamWConfig(state_dtype=state_dtype)
            opt_s = jax.eval_shape(
                functools.partial(adamw.init_state, opt_cfg), params_s
            )
            o_shard = sh.opt_state_sharding(opt_s, params_s, mesh, cfg=cfg)
            batch_s = S.batch_specs(cfg, batch, seq)
            b_shard = sh.batch_sharding(batch_s, mesh)
            accum = variant.get("accum_steps",
                                pick_accum_steps(batch, seq, mesh))
            record["accum_steps"] = accum
            record["variant"] = {k: v for k, v in variant.items()}
            import jax.numpy as _jnp
            gd = variant.get("grad_dtype")
            step = steps_mod.make_train_step(
                model, opt_cfg, accum_steps=accum, grad_shardings=p_shard,
                grad_compression=variant.get("grad_compression"),
                grad_dtype=getattr(_jnp, gd) if gd else None,
            )
            metrics_sh = None  # let XLA place scalars
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, metrics_sh),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_s, opt_s, batch_s)
        elif kind == "prefill":
            batch_s = S.infer_batch_specs(cfg, batch, seq)
            b_shard = sh.batch_sharding(batch_s, mesh)
            cap = seq + (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
            step = steps_mod.make_prefill_step(model, cap=cap)
            cache_s = jax.eval_shape(step, params_s, batch_s)[0]
            c_shard = sh.cache_sharding(cache_s, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, b_shard),
                out_shardings=(c_shard, None, None),
            )
            lowered = jitted.lower(params_s, batch_s)
        else:  # decode
            cache_s = S.abstract_cache(model, batch, seq)
            c_shard = sh.cache_sharding(cache_s, mesh)
            dspec = S.decode_specs(cfg, batch, seq)
            tok_shard = sh.batch_sharding({"tokens": dspec["tokens"]}, mesh)[
                "tokens"
            ]
            step = steps_mod.make_serve_step(model)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, tok_shard, sh.replicated(mesh)),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_s, cache_s, dspec["tokens"],
                                   dspec["pos"])

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)  # while bodies counted ONCE (diagnostic)
    scaled = loop_scaled_collectives(hlo)  # trip-count corrected (§Roofline)
    flops, acc_bytes = cost.get("flops", 0.0), cost.get("bytes accessed", 0.0)

    record.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        hlo_flops=flops,
        hlo_bytes=acc_bytes,
        collective_counts=coll.counts,
        collective_operand_bytes=coll.operand_bytes,
        collective_link_bytes=coll.link_bytes,
        collective_operand_bytes_scaled=scaled.operand_bytes,
        collective_link_bytes_scaled=scaled.link_bytes,
        collective_counts_scaled=scaled.counts,
        mem=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            code_bytes=mem.generated_code_size_in_bytes,
        ),
    )
    if verbose:
        print(
            f"[{arch} × {shape_name} × {record['mesh']}] compile ok "
            f"({t_lower:.1f}s lower / {t_compile:.1f}s compile)\n"
            f"  memory/device: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
            f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
            f"alias={mem.alias_size_in_bytes/2**30:.2f}GiB\n"
            f"  HLO flops={flops:.3e} bytes={acc_bytes:.3e} "
            f"collective_operand={coll.total_operand_bytes:.3e}B "
            f"counts={ {k: v for k, v in coll.counts.items() if v} }"
        )
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--schedule-db", default=None,
                    help="warm repro.tuna schedule DB (JSONL) for trace-time "
                         "kernel block-spec picks; only consulted when "
                         "kernels lower for TPU (host-forced CPU compiles "
                         "take the jnp reference path)")
    args = ap.parse_args()

    if args.schedule_db:
        from repro.kernels.ops import use_schedule_db

        use_schedule_db(args.schedule_db)
        if jax.default_backend() != "tpu":
            print("[tuna] note: --schedule-db installed, but this dry run "
                  "compiles on the CPU backend where kernels use the "
                  "reference path; block-spec picks are not exercised")
    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if args.all or not args.arch else [args.arch]
    shapes = list(S.SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape_name}_{'multipod' if mp else 'pod'}"
                try:
                    rec = run_cell(arch, shape_name, multi_pod=mp)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {
                        "arch": arch,
                        "shape": shape_name,
                        "multi_pod": mp,
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append(tag)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2, default=float)
    if failures:
        print(f"FAILED cells: {failures}")
        raise SystemExit(1)
    print("all requested cells passed")


if __name__ == "__main__":
    main()
