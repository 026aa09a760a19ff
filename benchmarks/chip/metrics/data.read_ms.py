"""Host time in the loader's ``next()`` (``TokenLoader`` over ``StripeStore.read_item``),
summed over the window, per step."""


def read(rec):
    return rec["spans"]["data.read"] / rec["steps"] * 1e3
