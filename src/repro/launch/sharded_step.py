"""The sharded step, built one way for the dry-run, the chip smoke and tests.

``build_sharded_step`` returns the step jitted with explicit in/out
shardings on ``mesh`` — the model layout's specs for params, ZeRO specs for
the optimizer state, data-parallel specs for the batch — together with its
abstract arguments (ShapeDtypeStructs, no allocation).

ZeRO (``train/optimizer.py`` ``opt_state_specs``) shards a layer-stacked
leaf's state on a dimension within the layer, not on the scan axis that
``models/params.py`` ``stack`` prepends (unless no other dimension
divides).  The backward layer scan yields one layer's gradient per
iteration: with the state split by layer, the partitioner all-reduces that
whole gradient on every chip inside the loop, exposed; split within the
layer, the gradient leaves the loop as reduce-scatters that XLA overlaps
with the weight-gradient matmuls, and the updated parameters are
all-gathered after AdamW.  Lower and compile it
from the abstract arguments, or call it on arrays placed with its shardings.

Importing this module touches no device and writes no environment.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import build_model, params as PM
from ..models.registry import input_specs, step_fn
from ..train.optimizer import AdamWConfig, opt_state_specs
from ..train.step import make_train_step


def abstract_opt_state(layout, opt_cfg: AdamWConfig):
    """ShapeDtypeStruct opt state matching init_opt_state's structure."""
    f32 = lambda i: jax.ShapeDtypeStruct(i.shape, jnp.float32)
    is_info = lambda x: isinstance(x, PM.ParamInfo)
    state = {
        "mu": jax.tree.map(f32, layout, is_leaf=is_info),
        "nu": jax.tree.map(f32, layout, is_leaf=is_info),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if opt_cfg.master_fp32:
        state["master"] = jax.tree.map(f32, layout, is_leaf=is_info)
    return state


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


@dataclass
class ShardedStep:
    layout: Any
    jitted: Any                 # train: (params, opt, batch); else (params, batch)
    args: tuple                 # abstract arguments, in call order
    param_sharding: Any
    batch_sharding: Any
    opt_sharding: Any = None    # train steps only


def build_sharded_step(cfg, shape, mesh, opt_cfg: Optional[AdamWConfig] = None) -> ShardedStep:
    model = build_model(cfg, mesh=mesh, model_axis=mesh.shape["model"])
    layout = model.layout()
    params_abs = PM.abstract(layout, cfg.dtype)
    param_sh = _named(mesh, PM.specs(layout))
    batch_abs, batch_spec = input_specs(cfg, shape, mesh=mesh, model=model)
    batch_sh = _named(mesh, batch_spec)
    if shape.kind != "train":
        jitted = jax.jit(step_fn(cfg, shape, model=model), in_shardings=(param_sh, batch_sh))
        return ShardedStep(layout, jitted, (params_abs, batch_abs), param_sh, batch_sh)
    opt_cfg = opt_cfg or AdamWConfig()
    opt_sh = _named(mesh, opt_state_specs(layout, mesh, opt_cfg))
    jitted = jax.jit(
        make_train_step(model, opt_cfg),
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=(0, 1),
    )
    args = (params_abs, abstract_opt_state(layout, opt_cfg), batch_abs)
    return ShardedStep(layout, jitted, args, param_sh, batch_sh, opt_sh)
