"""Pallas TPU kernels (pl.pallas_call + BlockSpec VMEM tiling).

Each kernel ships with a pure-jnp oracle in ``ref.py`` and takes an explicit
``interpret`` argument (default off).  Validated by shape/dtype sweeps in
``tests/test_kernels.py``, which pass ``interpret=True`` on the CPU.
"""

from . import ref
from .cost import (
    KernelCost,
    flash_attention_cost,
    mlstm_scan_cost,
    ssd_scan_cost,
    swiglu_cost,
)
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .mlstm_scan import mlstm_scan
from .rmsnorm import rmsnorm
from .ssd_scan import ssd_scan_kernel
from .swiglu import swiglu_mlp

__all__ = [
    "KernelCost", "decode_attention", "flash_attention",
    "flash_attention_cost", "mlstm_scan", "mlstm_scan_cost", "ref",
    "rmsnorm", "ssd_scan_kernel", "ssd_scan_cost", "swiglu_cost",
    "swiglu_mlp",
]
