"""Read amplification of the loader over the window: chunk bytes read from
disk (``stripe.bytes_read``, fallback reads included) over the item bytes
``read_item`` delivered (``stripe.bytes_delivered``), from the program's own
counters (``repro.core.hostspans``) of the last ``steps`` batches."""


def read(rec):
    try:
        from repro.core import hostspans
    except ImportError:         # a program without the recorder
        return None
    batches = hostspans.last(rec["steps"])
    return None if batches is None else hostspans.read_amplification(batches)
