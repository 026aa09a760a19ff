"""Initial weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights: from the reference's
parameter spec (leaf -> shape and init) and ``--seed``.  Leaf ``i`` in the
spec's flattening order draws from ``fold_in(key(seed), i)``, so the same
seed gives the same weights to the program and to the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def shapes(spec) -> dict:
    return jax.tree.map(lambda s: s[0], spec, is_leaf=_is_leaf)


def init_fn(spec, dtype: str):
    """``seed -> params``: a pure function of the seed, to jit or inline."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    def init(seed):
        key = jax.random.key(seed)
        out = []
        for i, (shape, how) in enumerate(leaves):
            if how == "ones":
                out.append(jnp.ones(shape, dt))
            else:
                draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out.append((draw * how).astype(dt))
        return jax.tree.unflatten(treedef, out)

    return init


def make(spec, seed: int, dtype: str, sharding=None):
    return jax.jit(init_fn(spec, dtype), out_shardings=sharding)(jnp.uint32(seed % 2**32))
