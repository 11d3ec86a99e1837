"""Bring-up check on the chip: the system's main path, once, at full width.

    python chip_smoke.py              # one chip: serve yi-6b
    python chip_smoke.py --chips 4    # four chips: sharded training only

With no option it builds yi-6b (32 layers, d_model 4096, 32 query and 4 KV
heads, head_dim 128, d_ff 11008, vocab 64000, bf16) from random weights
made from ``--seed`` and serves 4 requests of 512 prompt tokens and 16 new
tokens through ``launch/serve.py:serve()`` with the continuous engine on 2
slots. It then checks that the Pallas flash kernel ran (trace count), that
it agrees with the jnp oracle at the prefill shape, and that every request's
tokens match the one-request-at-a-time greedy oracle.

With ``--chips 4`` it trains yi-6b cut to 2 layers for 3 steps through
``launch/train.py:train()`` on a (data 2, model 2) mesh, then the same 3
steps on one device, and checks that the losses agree, that a large
parameter leaf has a shard on every device, and that the state was created
sharded (device 0's peak memory after building it stays far below the
whole state's size).

Everything runs in this one process: a chip belongs to one process at a
time. It exits non-zero, without the result line, when JAX finds no TPU,
when the device kind is not in ``repro.hw.DEVICE_KINDS``, or when any check
fails. On success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# serving shape (the prefill takes the Pallas flash path: S < 4096)
ARCH = "yi-6b"
N_REQUESTS, SLOTS, PROMPT_LEN, MAX_NEW = 4, 2, 512, 16
# flash vs oracle at the prefill shape: the kernel feeds bf16 probabilities
# to its P·V matmul where the oracle keeps f32, so allow a few bf16 ulps
FLASH_ATOL = FLASH_RTOL = 2e-2
# sharded training: 2 layers of the full-width model, 3 steps
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 2, 3, 4, 512
# sharded vs one-device loss: reduction order differs under the mesh and
# parameters are bf16; two bf16 ulps of the loss
LOSS_RTOL = 2.0 ** -7


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"[chip_smoke] ok: {what}")


class CompileClock:
    """Sums XLA backend compile time (persistent-cache reads included)."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class _CompiledOracle:
    """The model's prefill and decode step, each compiled once, for
    ``greedy_decode_reference`` (op-by-op dispatch at full width would
    recompile the layer scan on every call).

    The request decodes alone, in row 0 of a batch as wide as the engine's,
    at its own position; the other rows idle at token 0 and position 0, as
    the engine pins a free slot. XLA on the TPU tiles a decode batch of one
    differently from a batch of two: on a v5e the final bf16 hidden state
    moved by up to 0.125 between them, enough to flip a near-tied greedy
    token, while the engine's per-slot positions left it bit-identical."""

    def __init__(self, model, width: int):
        import jax
        import jax.numpy as jnp

        def widen(cache):
            return jax.tree.map(lambda a: jnp.pad(
                a, [(0, 0), (0, width - 1)] + [(0, 0)] * (a.ndim - 2)), cache)

        def decode_step(params, cache, token, pos):
            logits, cache = model.decode_step(
                params, cache, jnp.pad(token, (0, width - 1)),
                jnp.pad(pos, (0, width - 1)))
            return logits[:1], cache

        self._prefill = jax.jit(model.prefill, static_argnums=2)
        self._widen = jax.jit(widen)  # cache leaves are [G, B, ...]
        self.decode_step = jax.jit(decode_step)

    def prefill(self, params, batch, cap):
        cache, pos, last = self._prefill(params, batch, cap)
        return self._widen(cache), pos, last


def _oracle_logits(oracle, params, prompt, tokens, i, cap):
    """Oracle logits for the token at index ``i`` after ``tokens[:i]``."""
    import jax.numpy as jnp

    cache, pos, last = oracle.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cap)
    logits = last[0, 0]
    for t in range(i):
        step, cache = oracle.decode_step(
            params, cache, jnp.asarray([tokens[t]], jnp.int32),
            jnp.full((1,), pos + t, jnp.int32))
        logits = step[0]
    return logits


def serve_phase(device, target, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.launch.engine import Request, greedy_decode_reference
    from repro.launch.serve import serve
    from repro.models.model import Model

    cfg = get_config(ARCH)
    model = Model(cfg)
    params = jax.block_until_ready(model.init(jax.random.key(seed)))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[chip_smoke] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.param_dtype}; parameter bytes {param_bytes}")

    rng = np.random.default_rng(seed)
    requests = [Request(i, [int(t) for t in rng.integers(0, cfg.vocab, PROMPT_LEN)],
                        MAX_NEW) for i in range(N_REQUESTS)]
    cap = PROMPT_LEN + MAX_NEW
    ops.reset_pallas_trace_counts()
    stats = serve(model, params, requests, slots=SLOTS, cap=cap)
    print(f"[chip_smoke] served {len(requests)} requests, {stats['tokens']} "
          f"tokens: {stats['prefills']} prefills, {stats['engine_steps']} "
          f"decode steps on {SLOTS} slots")
    check(all(len(r.out) == MAX_NEW for r in requests),
          f"every request got {MAX_NEW} tokens")
    flash_traces = ops.pallas_trace_counts()["flash"]
    check(flash_traces > 0, f"prefill traced the Pallas flash kernel "
          f"({flash_traces} traces)")

    # flash kernel vs the jnp oracle, called directly, at the prefill shape
    blocks = ops.tuned_flash_blocks(PROMPT_LEN, cfg.head_dim, 2)
    kq, kk, kv = jax.random.split(jax.random.key(seed + 1), 3)
    q = jax.random.normal(kq, (1, cfg.n_heads, PROMPT_LEN, cfg.head_dim), jnp.bfloat16)
    k = jax.random.normal(kk, (1, cfg.n_kv_heads, PROMPT_LEN, cfg.head_dim), jnp.bfloat16)
    v = jax.random.normal(kv, (1, cfg.n_kv_heads, PROMPT_LEN, cfg.head_dim), jnp.bfloat16)
    got = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1]))(q, k, v)
    want = jax.jit(lambda q, k, v: ref.attention(q, k, v, causal=True))(q, k, v)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    check(bool(np.allclose(got, want, atol=FLASH_ATOL, rtol=FLASH_RTOL)),
          f"flash {target.name} blocks {blocks} vs oracle at {q.shape}: "
          f"max abs err {err} (atol {FLASH_ATOL}, rtol {FLASH_RTOL})")

    oracle = _CompiledOracle(model, SLOTS)
    for r in requests:
        want = greedy_decode_reference(oracle, params, r.prompt, MAX_NEW, cap)
        if r.out != want:
            i = next(j for j, (a, b) in enumerate(zip(r.out, want)) if a != b)
            logits = np.asarray(
                _oracle_logits(oracle, params, r.prompt, want, i, cap), np.float32)
            raise CheckFailed(
                f"request {r.rid}: engine token {r.out[i]} != oracle token "
                f"{want[i]} at index {i}; oracle logit margin there "
                f"{float(logits[want[i]] - logits[r.out[i]])}")
    print(f"[chip_smoke] ok: all {len(requests)} requests match the "
          f"one-request greedy oracle token for token")

    mem = device.memory_stats()
    print(f"[chip_smoke] peak_bytes_in_use {mem['peak_bytes_in_use']} of "
          f"bytes_limit {mem['bytes_limit']}")
    check(mem["peak_bytes_in_use"] < mem["bytes_limit"],
          "parameters, cache and steps fit the chip's memory")


def train_phase(devices, seed: int) -> None:
    import jax

    from repro.configs.base import get_config
    from repro.launch import mesh as mesh_mod
    from repro.launch.train import TrainOptions, build_state, train
    from repro.models.model import Model
    from repro.optim import adamw

    check(len(devices) == 4, f"4 devices visible ({len(devices)})")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=TRAIN_LAYERS)
    # bf16 moments: the one-device comparison run has to hold two copies of
    # the state (no donation) next to its gradients on one chip
    opts = TrainOptions(steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                        seed=seed, log_every=1, state_dtype="bfloat16",
                        mesh_shape=(2, 2))

    mesh = mesh_mod.make_mesh(opts.mesh_shape, ("data", "model"))
    params, opt_state = build_state(
        Model(cfg), adamw.AdamWConfig(lr=opts.lr, state_dtype=opts.state_dtype),
        seed, mesh)
    jax.block_until_ready((params, opt_state))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves((params, opt_state)))
    peak0 = devices[0].memory_stats()["peak_bytes_in_use"]
    print(f"[chip_smoke] train state {state_bytes} bytes; device 0 peak after "
          f"building it sharded: {peak0}")
    check(peak0 < state_bytes / 2,
          "state was created sharded, never whole on device 0")
    leaf = params["layers"][0]["mlp"]["w1"]
    shard_devices = {s.device for s in leaf.addressable_shards}
    check(shard_devices == set(devices)
          and all(s.data.size < leaf.size for s in leaf.addressable_shards),
          f"mlp w1 {leaf.shape} has a shard on each of the 4 devices "
          f"({leaf.sharding.spec})")
    del params, opt_state

    sharded = [loss for _, loss, _ in train(cfg, opts)["history"]]
    single = [loss for _, loss, _ in
              train(cfg, dataclasses.replace(opts, mesh_shape=None))["history"]]
    print(f"[chip_smoke] losses on the (2, 2) mesh {sharded}; on one device "
          f"{single}")
    check(len(sharded) == len(single) == TRAIN_STEPS
          and all(abs(a - b) <= LOSS_RTOL * abs(b) for a, b in zip(sharded, single)),
          f"sharded and one-device losses agree within rtol {LOSS_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    from repro.hw import target_for_device_kind

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX runs on {device.platform!r}",
              file=sys.stderr)
        return 2
    target = target_for_device_kind(device.device_kind)
    print(f"[chip_smoke] {len(devices)} x {device.device_kind} -> "
          f"{target.name}; compile cache {cache_dir}")

    # schedules come from the static tuner at trace time, never from a
    # store outside the checkout named by the environment
    from repro.core import tuner

    tuner.set_default_db(None)
    tuner.set_default_cache(None)
    tuner.set_default_bundle(None)
    tuner.set_default_learned(None)

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        train_phase(devices, args.seed)
    else:
        serve_phase(device, target, args.seed)
    print(f"[chip_smoke] compile seconds {clock.seconds} over {clock.count} "
          f"compilations; wall seconds {time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except CheckFailed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        sys.exit(1)
