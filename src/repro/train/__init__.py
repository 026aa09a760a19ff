"""Training substrate: step, optimizer (ZeRO), checkpointing, fault tolerance."""

from .checkpoint import CheckpointManager, SamplerState, config_digest
from .fault import PreemptionGuard, RestartPolicy, StragglerMonitor, run_with_restarts
from .hoardckpt import HoardCheckpointManager
from .optimizer import (
    AdamWConfig,
    adamw_update,
    compress_int8,
    decompress_int8,
    init_opt_state,
    opt_state_specs,
    zero_placement_tally,
    zero_spec_for,
)
from .step import (
    compiled_step_costs,
    compiled_step_flops,
    init_train_state,
    make_eval_step,
    make_train_step,
    token_batch_from_bytes,
)

__all__ = [
    "AdamWConfig", "CheckpointManager", "HoardCheckpointManager",
    "PreemptionGuard", "RestartPolicy",
    "SamplerState", "StragglerMonitor", "adamw_update", "compiled_step_costs",
    "compiled_step_flops",
    "compress_int8",
    "config_digest", "decompress_int8", "init_opt_state", "init_train_state",
    "make_eval_step", "make_train_step", "opt_state_specs",
    "run_with_restarts", "token_batch_from_bytes", "zero_placement_tally", "zero_spec_for",
]
