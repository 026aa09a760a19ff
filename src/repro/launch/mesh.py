"""Meshes: 16x16 single pod, 2x16x16 multi-pod, small test/host meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — the dry-run must set
``XLA_FLAGS`` before the first jax call.

Every axis is ``AxisType.Auto``: the step is jitted with explicit
``in_shardings``/``out_shardings`` and GSPMD propagates the rest.  (Since jax
0.7 ``jax.make_mesh`` defaults to Explicit axes, which reject that style.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pods: int = 0, devices=None):
    """Small meshes: CPU tests (virtual host devices) or one host's chips."""
    if pods:
        return _mesh((pods, data, model), ("pod", "data", "model"), devices)
    return _mesh((data, model), ("data", "model"), devices)
