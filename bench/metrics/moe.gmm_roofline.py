"""Least time of the ``moe_gmm`` kernel's calls in the decode program over
their device time, in percent. The least time is the larger of the bytes
over the memory bandwidth and the operations over the peak: the weights of
the (layer, expert) pairs that the traced batch's live rows touched, once,
and the routed pairs' activations in and out; 6 x d_model x d_expert
operations per routed pair (the configuration's work counts, ``gmm_least``,
from the engine's MoE counters over the traced batch). Calls in the prefill
program are left out: their routing is not counted. None where the program
has no such kernel or counters."""


def read(rec):
    calls, seconds = rec.get("gmm_decode", (0, 0.0))
    e = rec.get("traced_engine", {})
    if not calls or not seconds or not e.get("moe_pairs") or "work" not in rec:
        return None
    pk = rec["peaks"]
    least = rec["work"].gmm_least(rec["cfg"], e["moe_pairs"],
                                  e["moe_experts_touched"], pk.bf16_flops,
                                  pk.hbm_bytes_s)
    return 100.0 * least / seconds
