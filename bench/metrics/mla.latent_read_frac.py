"""Share of the latent cache one layer's decode attention reads, over the
window's decode steps: the engine's ``latent_rows_read`` (each slot's
blocks of rows below its position, what the ``latent_decode`` kernel
copies) over ``latent_rows_cap`` (every slot's whole capacity, what the
einsums over all ``cap`` rows read twice). None where the engine keeps no
such counters."""


def read(rec):
    e = rec["engine"]
    if not e.get("latent_rows_cap"):
        return None
    return e["latent_rows_read"] / e["latent_rows_cap"]
