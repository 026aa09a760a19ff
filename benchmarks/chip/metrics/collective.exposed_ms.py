"""Device time per step in which a collective runs on a chip and no compute
does (``trace_reduce.Reduction.exposed_collective_s``): the largest over the
chips, over the window's steps."""


def read(rec):
    exposed = rec["trace"].exposed_collective_s
    return max(exposed) / rec["steps"] * 1e3 if exposed else None
