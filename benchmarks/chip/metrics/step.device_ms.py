"""Device busy time per step: the union of the device's op intervals in the
traced window, averaged over the chips, over the window's steps."""


def read(rec):
    return rec["trace"].mean_busy_s / rec["steps"] * 1e3
