"""Continuous-batching serve engine: a slot state machine.

Per-slot scheduling (design notes: README "Serving"):

* every slot carries its own position — ``Model.decode_step`` takes a
  ``[B]`` pos vector, so rows at different sequence depths share one
  decode launch;
* an **admission queue** holds waiting requests (earliest deadline first,
  FIFO among equal deadlines) and refills a slot the moment it frees
  (EOS, ``max_new``, or deadline) — prefill runs on a batch of one and its
  KV/state cache is scattered into the live cache at the free slot index;
* free slots keep decoding (the batch shape is static) but their rows are
  masked out of every report: ``wasted_slot_steps`` counts exactly those
  slot-steps, which is the quantity continuous batching drives down.

Per-request measurement: TTFT (submit -> first token) and end-to-end
latency, aggregated to p50/p95/p99 by ``latency_summary``.

Host time: every iteration of ``run`` is a profiler step (``engine.step``)
cut into host spans that nest strictly on the engine's thread (README
"Tracing the engine"): ``engine.admit`` (``engine.prefill``,
``engine.first_token``, ``engine.insert``), ``engine.launch``,
``engine.sync`` and ``engine.emit``. The spans of a request carry its
``rid``. The four phases also add their seconds to
``stats()`` (``admit_s``, ``launch_s``, ``sync_s``, ``emit_s``), which
together cover ``run_s``.

MoE counters: for a model with experts the decode step also adds, on the
device, the routed pairs, the (layer, expert) pairs that received a token
and the busiest expert's tokens per layer into a counter array held beside
the cache; ``run`` reads it once, when it returns (``moe_pairs``,
``moe_experts_touched``, ``moe_max_load`` in ``stats()``; 0 for a dense
model, whose programs carry no counters).

Latent-attention counters: for a model with latent attention, each decode
launch adds, on the host from ``pos``, the latent cache rows one layer's
``latent_decode`` kernel reads (each slot's blocks below its position, a
free slot none) and the rows of one layer's whole cache
(``latent_rows_read``, ``latent_rows_cap`` in ``stats()``; absent for a
model without latent attention).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.latent_decode import block_rows
from repro.models.moe import N_STATS


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    eos_id: Optional[int] = None      # finish early when emitted
    deadline_s: Optional[float] = None  # wall budget from submission
    # measurement (filled by the engines; relative to serve() start)
    t_submit: float = 0.0
    t_first: Optional[float] = None   # TTFT instant
    t_done: Optional[float] = None
    truncated: bool = False           # deadline fired before max_new/EOS

    def finished(self) -> bool:
        return self.t_done is not None

    def wants_more(self) -> bool:
        return len(self.out) < self.max_new and not self.truncated and (
            self.eos_id is None or self.eos_id not in self.out)


def latency_summary(values: List[float]) -> Dict[str, float]:
    """p50/p95/p99 (+ mean) over per-request seconds."""
    if not values:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "mean": 0.0}
    a = np.asarray(values, np.float64)
    return {"p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(a.mean())}


def request_stats(requests: List[Request]) -> Dict:
    """Per-request rows + aggregated TTFT / e2e latency percentiles."""
    rows, ttfts, lats = [], [], []
    for r in requests:
        ttft = None if r.t_first is None else r.t_first - r.t_submit
        lat = None if r.t_done is None else r.t_done - r.t_submit
        if ttft is not None:
            ttfts.append(ttft)
        if lat is not None:
            lats.append(lat)
        rows.append({"rid": r.rid, "prompt_len": len(r.prompt),
                     "max_new": r.max_new, "tokens": len(r.out),
                     "ttft_s": ttft, "latency_s": lat,
                     "truncated": r.truncated})
    return {"requests": rows, "ttft_s": latency_summary(ttfts),
            "latency_s": latency_summary(lats)}


def greedy_decode_reference(model, params, prompt: List[int], max_new: int,
                            cap: int, eos_id: Optional[int] = None) -> List[int]:
    """One-request-at-a-time greedy decode (a batch of one, its position a
    [1] vector) — the oracle the engine must match token-for-token."""
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    cache, pos, last_logits = model.prefill(params, batch, cap)
    tok = int(jnp.argmax(last_logits[0, 0]))
    out = [tok]
    for t in range(max_new - 1):
        if eos_id is not None and out[-1] == eos_id:
            break
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.full((1,), pos + t, jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    if eos_id is not None and eos_id in out:
        out = out[: out.index(eos_id) + 1]
    return out


def _write_slot(live, one, i):
    """One request's prefilled cache into the live cache at slot index i:
    every leaf is [G, B, ...] (batch axis 1), so one dynamic_update_slice
    per leaf replaces the whole slot slice."""
    return jax.tree.map(lambda a, b: jax.lax.dynamic_update_slice_in_dim(
        a, b.astype(a.dtype), i, axis=1), live, one)


def counted(model) -> bool:
    """Whether the engine's decode state carries MoE counters."""
    return model.cfg.moe is not None


def latent_block(model, cap: int) -> int:
    """Cache rows the latent-attention kernel reads at a time; 0 for a model
    without latent attention."""
    cfg = model.cfg
    if cfg.mla is None:
        return 0
    return block_rows(cap, cfg.mla.cache_dim,
                      jnp.dtype(cfg.jnp_compute_dtype()).itemsize)


def init_state(model, slots: int, cap: int):
    """The live decode state the programs take: the cache, and for a model
    with experts (the cache, the counters [moe.N_STATS] int32)."""
    cache = model.init_cache(slots, cap)
    return (cache, jnp.zeros((N_STATS,), jnp.int32)) if counted(model) else cache


def programs(model, cap: int):
    """The engine's three jitted programs: ``prefill(params, batch)``,
    ``decode(params, state, tok, pos)`` and ``insert(live, one, i)`` over
    the state ``init_state`` makes. The decode step and the insert take the
    live state as donated, so each writes its few rows in place and no
    second cache is ever allocated. Their names (``jit_prefill``,
    ``jit_decode_step``, ``jit_insert``) are what a device trace shows them
    by. A model with experts adds the step's counters to the state's, over
    the rows not at position 0 (a free slot pins pos 0)."""
    def prefill(params, batch):
        return model.prefill(params, batch, cap)

    def insert(live, one, i):
        if counted(model):
            return _write_slot(live[0], one, i), live[1]
        return _write_slot(live, one, i)

    if not counted(model):
        return (jax.jit(prefill), jax.jit(model.decode_step, donate_argnums=1),
                jax.jit(insert, donate_argnums=0))

    def decode_step(params, state, tok, pos):
        cache, counts = state
        logits, cache, stats = model.decode_step_counted(params, cache, tok,
                                                         pos, pos > 0)
        return logits, (cache, counts + stats)

    return (jax.jit(prefill), jax.jit(decode_step, donate_argnums=1),
            jax.jit(insert, donate_argnums=0))


def _consumed(cache) -> bool:
    """Whether the call just dispatched took ``cache`` as donated: its
    buffers then belong to the call's result, written in place."""
    return jax.tree.leaves(cache)[0].is_deleted()


class _Phase:
    """Host span ``name`` (``meta`` as its metadata) whose seconds are added
    to ``counters[key]``."""
    __slots__ = ("_t", "_counters", "_key", "_span")

    def __init__(self, counters: Dict[str, float], key: str, name: str,
                 **meta):
        self._t = time.perf_counter()
        self._counters, self._key = counters, key
        self._span = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> None:
        self._span.__enter__()

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._counters[self._key] += time.perf_counter() - self._t


class _Slot:
    __slots__ = ("req", "deadline")

    def __init__(self):
        self.req: Optional[Request] = None
        self.deadline: Optional[float] = None  # absolute perf_counter time

    @property
    def free(self) -> bool:
        return self.req is None


class ContinuousEngine:
    """Slot state machine over a live decode cache of width ``slots``.

    Invariants (see README "Serving"):
      * a FREE slot's cache content is garbage — refill overwrites the
        whole slot slice (every cache leaf, along the batch axis) at
        prefill-scatter time, so nothing leaks between tenants;
      * ``pos[i]`` is the write index of slot i's *next* token; free slots
        pin pos=0 and tok=0 (their writes land in a slice that refill
        replaces, and the per-slot mask keeps them out of live rows);
      * a request holds its slot from admission until EOS / ``max_new`` /
        deadline, then the slot frees on the same engine step.
    """

    def __init__(self, model, params, slots: int, cap: int):
        self.model = model
        self.params = params
        self.slots = slots
        self.cap = cap

        self._prefill, self._decode, self._insert = programs(model, cap)
        self.cache = init_state(model, slots, cap)  # donated to each call
        self.pos = np.zeros(slots, np.int32)   # next write index per slot
        self.tok = np.zeros(slots, np.int32)   # last emitted token per slot
        self._slots = [_Slot() for _ in range(slots)]
        # stats
        self.engine_steps = 0        # decode launches
        self.slot_steps = 0          # slot-steps doing live work
        self.wasted_slot_steps = 0   # slot-steps on free slots
        self.prefills = 0
        self.deadline_truncations = 0
        self.cache_in_place = 0      # decodes/inserts given it as donated
        self.host_s = dict.fromkeys(
            ("run_s", "admit_s", "launch_s", "sync_s", "emit_s"), 0.0)
        self.moe = dict.fromkeys(
            ("moe_pairs", "moe_experts_touched", "moe_max_load"), 0)
        self._latent_blk = latent_block(model, cap)
        self.latent = (dict.fromkeys(("latent_rows_read", "latent_rows_cap"), 0)
                       if self._latent_blk else {})

    # ---------------------------------------------------------------- admit
    def _admit(self, slot_i: int, req: Request) -> None:
        span = jax.profiler.TraceAnnotation
        prompt = np.asarray(req.prompt, np.int32)[None]
        with span("engine.prefill", rid=req.rid, slot=slot_i,
                  prompt_len=len(req.prompt)):
            cache_1, pos_1, last_logits = self._prefill(
                self.params, {"tokens": jnp.asarray(prompt)})
        self.prefills += 1
        with span("engine.first_token", rid=req.rid):
            tok0 = int(jnp.argmax(last_logits[0, 0]))
        with span("engine.insert", rid=req.rid, slot=slot_i):
            live = self.cache
            self.cache = self._insert(live, cache_1, slot_i)
            self.cache_in_place += _consumed(live)
        slot = self._slots[slot_i]
        slot.req = req
        slot.deadline = (None if req.deadline_s is None
                         else req.t_submit + req.deadline_s)
        self.pos[slot_i] = int(pos_1)
        self.tok[slot_i] = tok0
        req.out.append(tok0)
        req.t_first = time.perf_counter() - self._t0
        self._maybe_finish(slot_i)

    def _maybe_finish(self, slot_i: int) -> None:
        slot = self._slots[slot_i]
        req = slot.req
        now = time.perf_counter() - self._t0
        if slot.deadline is not None and now >= slot.deadline and req.wants_more():
            req.truncated = True
            self.deadline_truncations += 1
        if not req.wants_more():
            req.t_done = now
            slot.req = None
            slot.deadline = None
            self.pos[slot_i] = 0
            self.tok[slot_i] = 0

    # ----------------------------------------------------------------- run
    def run(self, requests: List[Request]) -> None:
        """Serve ``requests`` to completion. Admission order is earliest
        deadline first (stable for equal/absent deadlines)."""
        self._t0 = time.perf_counter()
        try:
            queue = sorted(
                requests,
                key=lambda r: (r.deadline_s if r.deadline_s is not None
                               else float("inf")),
            )
            queue.reverse()  # pop() from the tail = earliest deadline
            step = 0
            while queue or any(not s.free for s in self._slots):
                with jax.profiler.StepTraceAnnotation("engine.step",
                                                      step_num=step):
                    step += 1
                    self._step(queue)
        finally:
            self.host_s["run_s"] += time.perf_counter() - self._t0
            if counted(self.model):  # the one readback of the counters
                self.moe = dict(zip(self.moe, np.asarray(self.cache[1]).tolist()))

    def _step(self, queue: List[Request]) -> None:
        """One iteration of ``run``: refill every free slot, then one decode
        step over the live slots."""
        if queue and any(s.free for s in self._slots):
            with _Phase(self.host_s, "admit_s", "engine.admit"):
                for i, s in enumerate(self._slots):
                    if s.free and queue:
                        self._admit(i, queue.pop())
        live = [i for i, s in enumerate(self._slots) if not s.free]
        if not live:
            return
        with _Phase(self.host_s, "launch_s", "engine.launch", live=len(live)):
            live_cache = self.cache
            logits, self.cache = self._decode(
                self.params, live_cache,
                jnp.asarray(self.tok), jnp.asarray(self.pos))
            self.cache_in_place += _consumed(live_cache)
            self.engine_steps += 1
            self.slot_steps += len(live)
            self.wasted_slot_steps += self.slots - len(live)
            if self.latent:
                blk = self._latent_blk
                self.latent["latent_rows_read"] += int(
                    ((self.pos + blk - 1) // blk).sum()) * blk
                self.latent["latent_rows_cap"] += self.slots * self.cap
        with _Phase(self.host_s, "sync_s", "engine.sync"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))  # one host sync
        with _Phase(self.host_s, "emit_s", "engine.emit"):
            for i in live:
                req = self._slots[i].req
                req.out.append(int(nxt[i]))
                self.tok[i] = int(nxt[i])
                self.pos[i] += 1
                self._maybe_finish(i)

    def stats(self) -> Dict:
        return {"engine_steps": self.engine_steps,
                "slot_steps": self.slot_steps,
                "wasted_slot_steps": self.wasted_slot_steps,
                "prefills": self.prefills,
                "deadline_truncations": self.deadline_truncations,
                "cache_in_place": self.cache_in_place,
                **self.host_s, **self.moe, **self.latent}
