"""The reference's blocks of sequences give the whole batch's loss and gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from tiny import tiny_cell
from weights import make


def test_blocks_of_sequences_match_the_whole_batch(monkeypatch):
    cell = tiny_cell()
    ref = harness.reference_module(cell)
    c = cell.config
    p = jax.tree.map(lambda x: x.astype(jnp.float32), make(ref.param_spec(c), 5, "float32"))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, c["vocab_size"], (8, 16), dtype=np.int32))
    labels = jnp.roll(tokens, -1, axis=1)
    whole = ref._value_and_grad(c, p, tokens, labels, False)
    monkeypatch.setattr(ref, "MICRO_TOKENS", 32)          # blocks of 2 sequences
    blocks = ref._value_and_grad(c, p, tokens, labels, False)
    assert float(blocks[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(blocks[1]), jax.tree.leaves(whole[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
