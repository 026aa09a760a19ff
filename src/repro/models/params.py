"""Parameter layout: one declarative tree yields init, abstract shapes, specs.

Every model describes its parameters as a pytree of :class:`ParamInfo`
(shape + PartitionSpec + initializer).  From that single source of truth we
derive:

* ``materialize(layout, key)``  — real arrays (smoke tests, examples),
* ``abstract(layout)``          — ``jax.ShapeDtypeStruct`` (dry-run: no alloc),
* ``specs(layout)``             — the matching PartitionSpec tree for pjit.

Stacked (scan-over-layers) blocks call :func:`stack` to prepend the layer
axis to every leaf (sharding ``None`` on that axis).  It is the one place
that adds a scan axis, and it counts them in ``ParamInfo.stacked``: a
layer scan slices those leading axes, which the ZeRO rule
(``train/optimizer.py`` ``zero_spec_for``) keeps the optimizer state off.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class ParamInfo:
    shape: tuple[int, ...]
    spec: P = P()
    init: str = "normal"           # normal | zeros | ones | small
    scale: Optional[float] = None  # stddev override; default 1/sqrt(fan_in)
    dtype: Optional[str] = None    # override model dtype (e.g. fp32 gates)
    stacked: int = 0               # leading scan axes, prepended by :func:`stack`


def _init_leaf(info: ParamInfo, key, dtype) -> jax.Array:
    dt = jnp.dtype(info.dtype or dtype)
    if info.init == "zeros":
        return jnp.zeros(info.shape, dt)
    if info.init == "ones":
        return jnp.ones(info.shape, dt)
    fan_in = info.shape[-2] if len(info.shape) >= 2 else max(1, info.shape[-1])
    std = info.scale if info.scale is not None else fan_in ** -0.5
    if info.init == "small":
        std = 0.02
    return (jax.random.normal(key, info.shape, jnp.float32) * std).astype(dt)


def _is_info(x) -> bool:
    return isinstance(x, ParamInfo)


def materialize(layout, key, dtype="bfloat16"):
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_info)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(info, k, dtype) for info, k in zip(leaves, keys)]
    )


def abstract(layout, dtype="bfloat16"):
    return jax.tree.map(
        lambda i: jax.ShapeDtypeStruct(i.shape, jnp.dtype(i.dtype or dtype)),
        layout,
        is_leaf=_is_info,
    )


def specs(layout):
    return jax.tree.map(lambda i: i.spec, layout, is_leaf=_is_info)


def stack(n: int, layout):
    """Prepend a stacked-layers axis to every leaf of ``layout``."""
    return jax.tree.map(
        lambda i: replace(i, shape=(n, *i.shape), spec=P(None, *i.spec),
                          stacked=i.stacked + 1),
        layout,
        is_leaf=_is_info,
    )


def param_count(layout) -> int:
    leaves = jax.tree.leaves(layout, is_leaf=_is_info)
    total = 0
    for info in leaves:
        c = 1
        for s in info.shape:
            c *= s
        total += c
    return total


def param_bytes(layout, dtype="bfloat16") -> int:
    leaves = jax.tree.leaves(layout, is_leaf=_is_info)
    total = 0
    for info in leaves:
        c = 1
        for s in info.shape:
            c *= s
        total += c * jnp.dtype(info.dtype or dtype).itemsize
    return total
