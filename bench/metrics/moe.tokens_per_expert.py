"""Routed pairs per (layer, expert) pair that received a token, over the
window's decode steps: the engine's ``moe_pairs`` over
``moe_experts_touched``. How many tokens each expert's weight read serves;
None where the engine keeps no such counters or routed nothing."""


def read(rec):
    e = rec["engine"]
    if not e.get("moe_experts_touched"):
        return None
    return e["moe_pairs"] / e["moe_experts_touched"]
