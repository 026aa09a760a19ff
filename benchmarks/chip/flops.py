"""Model FLOPs of a training step, and the chips' peak rates.

The rule: the operations the forward and backward passes require, 3 x (2 x
the matmul parameters) per token, plus the attention products (QK^T and PV)
over the positions a causal query can see.  The embedding lookup is not a
matmul and is not counted; a tied embedding counts once, as the output head.
Recomputation (rematerialisation) is not counted.  Each family applies the
rule to its own shapes in ``families/<family>.py`` (``flops_per_token``).
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
PEAKS = HERE / "peaks.json"


def family(c: dict):
    """``families/<family>.py`` of a configuration, by its ``program.family``."""
    name = c["program"]["family"]
    spec = importlib.util.spec_from_file_location(f"family_{name}",
                                                  HERE / "families" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def train_flops_per_token(c: dict, seq: int) -> float:
    return family(c).flops_per_token(c, seq)


def peak(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]
