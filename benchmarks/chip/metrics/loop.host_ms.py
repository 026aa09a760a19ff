"""Host time of the trainer loop's own calls per step: the batch's transfer
(``jnp.asarray`` or ``device_put``), the step's dispatch and the loss fetch."""


def read(rec):
    s = rec["spans"]
    return (s["h2d"] + s["dispatch"] + s["loss_fetch"]) / rec["steps"] * 1e3
