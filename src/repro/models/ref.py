"""Pure-jnp oracles of the model primitives (the allclose ground truth).

The oracle for ``models/layers.py`` (attention, decode attention, RMSNorm,
SwiGLU), ``models/xlstm.py`` (the chunked mLSTM) and ``models/hymba.py``.
Deliberately naive: full-materialisation softmax attention, direct per-step
recurrences, unfused norms.  Tests sweep shapes/dtypes and assert each
primitive against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal=True, window=0, valid_len=None):
    """q: (B,Hq,Sq,hd); k,v: (B,Hkv,Skv,hd).  Full softmax, GQA-aware."""
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Sq, hd).astype(jnp.float32) * (hd ** -0.5)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32))
    q_pos = jnp.arange(Sq)
    kv_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        # queries sit at the END of the kv sequence (prefill: Sq == Skv)
        offset = Skv - Sq
        mask &= kv_pos[None, :] <= (q_pos[:, None] + offset)
        if window > 0:
            mask &= kv_pos[None, :] > (q_pos[:, None] + offset - window)
    if valid_len is not None:
        mask &= (kv_pos < valid_len)[None, :]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, Sq, -1).astype(v.dtype)


def rmsnorm_ref(x, gamma, eps=1e-5):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * gamma


def swiglu_ref(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def mlstm_ref(q, k, v, i_raw, log_f):
    """Sequential stabilized mLSTM: the O(S) step-by-step ground truth.

    q,k: (B,H,S,dqk); v: (B,H,S,dv); i_raw, log_f: (B,H,S).
    """
    B, H, S, dqk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    qf = q.astype(f32) * (dqk ** -0.5)
    kf, vf = k.astype(f32), v.astype(f32)
    ii, lf = i_raw.astype(f32), log_f.astype(f32)

    def step(carry, xs):
        C, n, m = carry
        q_t, k_t, v_t, i_t, f_t = xs
        m_new = jnp.maximum(f_t + m, i_t)
        f_s = jnp.exp(f_t + m - m_new)
        i_s = jnp.exp(i_t - m_new)
        C = f_s[..., None, None] * C + i_s[..., None, None] * (
            k_t[..., :, None] * v_t[..., None, :]
        )
        n = f_s[..., None] * n + i_s[..., None] * k_t
        num = jnp.einsum("bhd,bhdv->bhv", q_t, C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q_t, n)), jnp.exp(-m_new))
        return (C, n, m_new), num / den[..., None]

    init = (
        jnp.zeros((B, H, dqk, dv), f32),
        jnp.zeros((B, H, dqk), f32),
        jnp.full((B, H), -1e30, f32),
    )
    xs = (
        jnp.moveaxis(qf, 2, 0),
        jnp.moveaxis(kf, 2, 0),
        jnp.moveaxis(vf, 2, 0),
        jnp.moveaxis(ii, 2, 0),
        jnp.moveaxis(lf, 2, 0),
    )
    _, hs = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(hs, 0, 2)                     # (B,H,S,dv)


def decode_attention_ref(q, k_cache, v_cache, valid_len):
    """q: (B,Hq,1,hd) against (B,Hkv,S,hd) caches, masked at valid_len."""
    return attention_ref(q, k_cache, v_cache, causal=False, valid_len=valid_len)
