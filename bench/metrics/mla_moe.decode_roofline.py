"""Least time of a decode step over its measured device time, in percent,
for a model with latent attention and routed experts. A step's least time
is the larger of its bytes over the memory bandwidth and its operations
over the peak: the weights outside the routed experts once (of the input
embedding only the rows looked up), the experts the live rows touched (the
engine's counters over the traced batch, per step: not all of them), and
each live slot's latent rows up to its position
(``decode_step_least`` of the configuration's work counts). The mean over
the traced steps, times the decode program's executions in the trace, over
their device time. None without the counters."""


def read(rec):
    n, seconds = rec.get("trace", {}).get("programs", {}).get(
        "jit_decode_step", (0, 0.0))
    steps = [p for p in rec.get("traced_decodes", []) if p]
    e = rec.get("traced_engine", {})
    if not n or not steps or not e.get("engine_steps") or "work" not in rec:
        return None
    pk, cfg = rec["peaks"], rec["cfg"]
    touched = e["moe_experts_touched"] / e["engine_steps"]
    least = sum(rec["work"].decode_step_least(cfg, p, touched, pk.bf16_flops,
                                              pk.hbm_bytes_s)
                for p in steps) / len(steps)
    return 100.0 * least * n / seconds
