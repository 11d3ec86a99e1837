"""Host milliseconds per decode step with nothing queued on the device: the
engine's launch phase (token and position upload, decode dispatch) plus its
emit phase (per-slot token append and finish checks), from its own counters
over the window. None where the engine keeps no such counters or took no
step."""


def read(rec):
    e = rec["engine"]
    if "launch_s" not in e or "emit_s" not in e or not e["engine_steps"]:
        return None
    return 1e3 * (e["launch_s"] + e["emit_s"]) / e["engine_steps"]
