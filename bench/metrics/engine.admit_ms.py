"""Host milliseconds per admission: the engine's admit phase (snapshot poll,
prefill dispatch, first-token readback, insert dispatch) over its prefills,
from its own counters over the window; how long live slots stall for each
request admitted. None where the engine keeps no such counter or admitted
nothing."""


def read(rec):
    e = rec["engine"]
    if "admit_s" not in e or not e["prefills"]:
        return None
    return 1e3 * e["admit_s"] / e["prefills"]
