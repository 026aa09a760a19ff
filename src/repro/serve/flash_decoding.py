"""Sequence-parallel decode attention: flash-decoding across chips.

The baseline decode path shards the KV cache's sequence dimension on the
``model`` axis and lets GSPMD partition the softmax; this module does it
*explicitly* with ``shard_map``: every chip computes a partial online-softmax
(m, l, acc) over its local KV shard, and partials merge with one small
all-reduce-style combine — the block algebra of flash attention's online
softmax, with one chip's KV shard as the block.

Why it matters at scale: GQA head counts in the pool (5, 10, 20, 25) do not
divide a 16-way TP axis, so head-sharding cannot cover decode; sequence
sharding works for every arch and keeps the per-chip cache slice O(S/16).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG = -1e30


def _partial_softmax(q, k_shard, v_shard, pos0, valid_len):
    """Per-chip partial attention.  q: (B,Hq,1,hd); shards: (B,Hkv,Sl,hd).

    Returns (m, l, acc): running max, denominator, unnormalised output.
    """
    B, Hq, _, hd = q.shape
    _, Hkv, Sl, _ = k_shard.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, 1, hd).astype(jnp.float32) * (hd ** -0.5)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_shard.astype(jnp.float32))
    pos = pos0 + jnp.arange(Sl)
    mask = pos < valid_len
    s = jnp.where(mask[None, None, None, None], s, _NEG)
    m = s.max(-1)                                            # (B,Hkv,G,1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_shard.astype(jnp.float32))
    return m, l, acc


def make_flash_decode(mesh, axis: str = "model"):
    """Returns fn(q, k_cache, v_cache, valid_len) with seq-sharded caches.

    q replicated over ``axis``; caches sharded P(..., axis, ...) on seq.
    The combine uses the flash merge: with global maximum m*,
    out = sum_i exp(m_i - m*) acc_i / sum_i exp(m_i - m*) l_i.
    """
    n_shards = mesh.shape[axis]

    def fn(q, k_cache, v_cache, valid_len):
        B, Hq, _, hd = q.shape

        def shard_fn(q, k_shard, v_shard, valid):
            idx = jax.lax.axis_index(axis)
            Sl = k_shard.shape[2]
            m, l, acc = _partial_softmax(q, k_shard, v_shard, idx * Sl, valid)
            m_star = jax.lax.pmax(m, axis)
            scale = jnp.exp(m - m_star)
            l_tot = jax.lax.psum(l * scale, axis)
            acc_tot = jax.lax.psum(acc * scale[..., None], axis)
            out = acc_tot / jnp.where(l_tot == 0, 1.0, l_tot)[..., None]
            return out.reshape(B, Hq, 1, hd).astype(v_shard.dtype)

        return jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(None, None, axis, None), P(None, None, axis, None), P()),
            out_specs=P(),
        )(q, k_cache, v_cache, valid_len)

    return fn
