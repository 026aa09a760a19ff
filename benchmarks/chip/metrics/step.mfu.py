"""The whole step's share of the chips' peak bf16 rate: model FLOPs of the
window's steps (``flops.py``) over device busy time x chips x peak."""

from flops import peak


def read(rec):
    busy = rec["trace"].mean_busy_s
    if busy <= 0:
        return None
    work = rec["flops_per_step"] * rec["steps"]
    return 100.0 * work / (busy * rec["chips"] * peak(rec["device_kind"])["bf16_flops_per_s"])
