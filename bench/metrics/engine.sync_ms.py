"""Host milliseconds per decode step spent in the engine's sync phase
(``engine.sync``: the argmax dispatch and the blocking readback of the
step's tokens), from its own counters over the window. None where the
engine keeps no such counter or took no step."""


def read(rec):
    e = rec["engine"]
    if "sync_s" not in e or not e["engine_steps"]:
        return None
    return 1e3 * e["sync_s"] / e["engine_steps"]
