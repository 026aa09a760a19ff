"""Host time per step in the program's span ``stripe.verify`` (the CRC32 of
each chunk read and its compare), from ``repro.core.hostspans``: summed over
the window's batches, the last ``steps`` the loader closed, over their
number."""


def read(rec):
    try:
        from repro.core import hostspans
    except ImportError:         # a program without the recorder
        return None
    batches = hostspans.last(rec["steps"])
    return None if batches is None else hostspans.per_batch_ms(batches, "stripe.verify")
