"""AdamW with ZeRO-style optimizer-state sharding + gradient compression.

No external optimizer dependency: the update is ~30 lines of jnp, which
lets us control sharding precisely.

ZeRO-1 (default): the fp32 (mu, nu, master) states — 12 bytes/param, the
dominant training memory — are sharded along the ``data`` axis on the first
dimension whose size divides it and is not already model-sharded, skipping
a layer-stacked leaf's scan axes (``ParamInfo.stacked``) unless no other
dimension divides.  Under SPMD the optimizer update then runs
data-parallel-sharded (each data shard updates its slice), which is exactly
the ZeRO-1 compute/memory split; pjit inserts the (reduce-scatter +
all-gather) pair where profitable.  Why not the scan axis: the backward
layer scan produces one layer's gradient per iteration, and a state that
gives each layer to one chip makes the partitioner all-reduce that layer's
whole gradient inside the loop, with nothing to overlap it.  Split within
each layer, the reduction becomes reduce-scatters that XLA overlaps with
the weight-gradient matmuls.

Gradient compression (pod axis / DCN): error-feedback int8 quantisation for
the cross-pod gradient reduction, used by the explicit shard_map DP path in
``repro.train.sync`` — DCN bandwidth is the scarce resource at multi-pod
scale, and 4x fewer bytes on the wire is the paper-era trick that still
holds.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import params as PM


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    master_fp32: bool = True


def _schedule(cfg: AdamWConfig, step):
    warm = jnp.minimum(1.0, (step + 1) / max(1, cfg.warmup_steps))
    return cfg.lr * warm


def init_opt_state(params, cfg: AdamWConfig):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    state = {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }
    if cfg.master_fp32:
        # copy=True: when params are already fp32, astype would alias the
        # param buffer and break donation (double-donate)
        state["master"] = jax.tree.map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params
        )
    return state


def adamw_update(grads, state, params, cfg: AdamWConfig):
    count = state["count"] + 1
    lr = _schedule(cfg, state["count"])

    # global-norm clip in fp32
    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    gnorm = jnp.sqrt(gsq)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))

    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(g, mu, nu, p, master):
        g = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g)
        mhat = mu / b1c
        nhat = nu / b2c
        base = master if master is not None else p.astype(jnp.float32)
        new = base - lr * (mhat / (jnp.sqrt(nhat) + cfg.eps) + cfg.weight_decay * base)
        return new, mu, nu

    flat_g, tdef = jax.tree.flatten(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    flat_p = jax.tree.leaves(params)
    flat_ms = jax.tree.leaves(state["master"]) if "master" in state else [None] * len(flat_p)

    new_p, new_mu, new_nu, new_ms = [], [], [], []
    for g, mu, nu, p, ms in zip(flat_g, flat_mu, flat_nu, flat_p, flat_ms):
        np_, nmu, nnu = upd(g, mu, nu, p, ms)
        new_p.append(np_.astype(p.dtype))
        new_mu.append(nmu)
        new_nu.append(nnu)
        new_ms.append(np_)

    out_state = {
        "mu": jax.tree.unflatten(tdef, new_mu),
        "nu": jax.tree.unflatten(tdef, new_nu),
        "count": count,
    }
    if "master" in state:
        out_state["master"] = jax.tree.unflatten(tdef, new_ms)
    return jax.tree.unflatten(tdef, new_p), out_state, {"grad_norm": gnorm, "lr": lr}


# ------------------------------------------------------------- ZeRO specs
def zero_spec_for(
    param_spec: P, shape: tuple[int, ...], data_size: int, axis: str = "data",
    stacked: int = 0, axis_sizes: Optional[dict] = None,
) -> P:
    """Add the ``data`` axis to the first unsharded, divisible dimension.

    For a leaf with ``stacked`` leading scan axes, the dimensions after them
    come first: an unsharded one, else (given the mesh's ``axis_sizes``) one
    whose mesh axes leave it divisible by ``data_size`` more, which then takes
    ``data`` after them, as ``("model", "data")``.  The scan axis is the last
    resort.  An unstacked leaf gets the first unsharded, divisible dimension.
    """
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))

    def fits(i: int, held: tuple = ()) -> bool:
        n = data_size * math.prod(axis_sizes[a] for a in held)
        return n > 0 and shape[i] % n == 0 and shape[i] >= n

    inner = range(stacked, len(shape))
    free = [i for i in inner if entries[i] is None and fits(i)]
    if stacked and not free and axis_sizes is not None:
        for i in inner:
            held = entries[i] if isinstance(entries[i], tuple) else (entries[i],)
            if entries[i] is not None and fits(i, held):
                entries[i] = (*held, axis)
                return P(*entries)
    free += [i for i in range(stacked) if entries[i] is None and fits(i)]
    if free:
        entries[free[0]] = axis
    return P(*entries)


def _data_size(mesh, axis: str) -> int:
    return mesh.shape[axis] if (mesh is not None and axis in mesh.axis_names) else 1


def _zspec(info: PM.ParamInfo, mesh, axis: str) -> P:
    sizes = dict(mesh.shape) if mesh is not None else None
    return zero_spec_for(info.spec, info.shape, _data_size(mesh, axis), axis, info.stacked, sizes)


def opt_state_specs(layout, mesh, cfg: AdamWConfig, axis: str = "data"):
    """Sharding-spec pytree matching ``init_opt_state``'s structure."""
    sharded = jax.tree.map(lambda i: _zspec(i, mesh, axis), layout,
                           is_leaf=lambda x: isinstance(x, PM.ParamInfo))
    state = {"mu": sharded, "nu": sharded, "count": P()}
    if cfg.master_fp32:
        state["master"] = sharded
    return state


def zero_placement_tally(layout, mesh, cfg: AdamWConfig, axis: str = "data") -> dict:
    """AdamW's fp32 state leaves (``mu``, ``nu``, ``master``) and their bytes
    (all shards together) by where ``opt_state_specs`` puts the ``data``
    axis: on a leaf's scan axis, on another of its axes, or nowhere (a mesh
    without the axis, or of size 1, or no dimension divides)."""
    data_size = _data_size(mesh, axis)
    states = 3 if cfg.master_fp32 else 2
    tally = {k: [0, 0] for k in ("scan_axis", "inner_axis", "replicated")}
    for info in jax.tree.leaves(layout, is_leaf=lambda x: isinstance(x, PM.ParamInfo)):
        on = [i for i, e in enumerate(_zspec(info, mesh, axis))
              if e == axis or (isinstance(e, tuple) and axis in e)]
        where = ("replicated" if data_size == 1 or not on
                 else "scan_axis" if on[0] < info.stacked else "inner_axis")
        tally[where][0] += states
        tally[where][1] += states * 4 * math.prod(info.shape)
    return {k: tuple(v) for k, v in tally.items()}


# ---------------------------------------------------- gradient compression
def compress_int8(g, error):
    """Error-feedback int8 quantisation: returns (q, scale, new_error)."""
    gf = g.astype(jnp.float32) + error
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-9) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    new_error = gf - q.astype(jnp.float32) * scale
    return q, scale, new_error


def decompress_int8(q, scale):
    return q.astype(jnp.float32) * scale
