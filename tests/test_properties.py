"""Hypothesis property tests on system invariants."""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import SimClock, Resource
from repro.core.loader import EpochPlan
from repro.models import ref
from repro.models.layers import band_pairs, blockwise_attention


@settings(max_examples=25, deadline=None)
@given(
    flows=st.lists(st.floats(10.0, 1e4), min_size=1, max_size=6),
    bw=st.floats(10.0, 1e3),
)
def test_flow_conservation(flows, bw):
    """Property: a single shared resource finishes total work at exactly
    sum(bytes)/bw regardless of flow mix (work conservation)."""
    clock = SimClock()
    r = Resource("r", bw)
    for nbytes in flows:
        clock.transfer([r], nbytes)
    clock.run()
    assert abs(clock.now - sum(flows) / bw) / (sum(flows) / bw) < 1e-6


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 1000), seed=st.integers(0, 2**31), epoch=st.integers(0, 5))
def test_epoch_plan_is_permutation(n, seed, epoch):
    """Every epoch order is a complete permutation (Req 2's premise)."""
    order = EpochPlan(n, seed).order(epoch)
    assert len(np.unique(order)) == n


@settings(max_examples=15, deadline=None)
@given(
    nq=st.integers(1, 6),
    nk=st.integers(1, 6),
    window_blocks=st.integers(0, 3),
)
def test_band_pairs_cover_exactly_visible_blocks(nq, nk, window_blocks):
    """Property: the static pair list contains exactly the (qi,kj) blocks
    intersecting the causal/window band — no more, no fewer."""
    qb = kb = 16
    window = window_blocks * kb if window_blocks else 0
    pairs = {tuple(p) for p in band_pairs(nq, nk, qb, kb, causal=True, window=window)}
    for qi in range(nq):
        for kj in range(nk):
            q_lo, q_hi = qi * qb, qi * qb + qb - 1
            k_lo, k_hi = kj * kb, kj * kb + kb - 1
            visible = k_lo <= q_hi and (window == 0 or k_hi > q_lo - window)
            assert ((qi, kj) in pairs) == visible, (qi, kj, visible)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 1000),
    s_blocks=st.integers(2, 4),
    causal=st.booleans(),
)
def test_attention_invariant_to_block_size(seed, s_blocks, causal):
    """Property: blockwise attention output is independent of tile size."""
    rng = np.random.default_rng(seed)
    S = 64 * s_blocks
    q = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    a = blockwise_attention(q, k, v, causal=causal, q_block=64, kv_block=64)
    b = blockwise_attention(q, k, v, causal=causal, q_block=32, kv_block=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_softmax_normalisation_of_attention(seed):
    """Rows of implied attention weights sum to 1: output of attending to
    constant V equals that constant."""
    rng = np.random.default_rng(seed)
    S = 128
    q = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
    v = jnp.ones((1, 2, S, 16), jnp.float32) * 3.5
    out = blockwise_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-5)
