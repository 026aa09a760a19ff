"""Share of the traced window in which no operation ran on the device,
averaged over the chips."""


def read(rec):
    return 100.0 * rec["trace"].idle_share
