"""Device milliseconds per prefill: the prefill program's (``jit_prefill``)
time in the trace over its executions there."""


def read(rec):
    n, seconds = rec.get("trace", {}).get("programs", {}).get(
        "jit_prefill", (0, 0.0))
    return 1e3 * seconds / n if n else None
