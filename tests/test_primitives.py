"""Model primitives against pure-jnp oracles: shapes x dtypes sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ref
from repro.models.layers import blockwise_attention, decode_attention, rms_norm, swiglu
from repro.models.xlstm import mlstm_chunked

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,hd,causal,window,bq,bk,pairs",
    [
        (2, 4, 2, 256, 256, 32, True, 0, 64, 64, True),
        (2, 4, 2, 256, 256, 32, True, 128, 64, 64, True),
        (2, 4, 2, 256, 256, 32, False, 0, 64, 64, False),
        (1, 2, 2, 128, 128, 32, True, 0, 64, 64, False),
        (2, 4, 2, 256, 256, 64, True, 0, 128, 64, False),
        (1, 8, 2, 192, 192, 32, True, 64, 64, 64, False),     # SWA + ragged blocks
        (2, 2, 2, 128, 256, 64, False, 0, 64, 128, False),    # cross attention
        (1, 4, 4, 100, 100, 16, True, 0, 64, 64, False),      # unaligned seq
    ],
)
def test_xla_blockwise_matches_oracle(dtype, B, Hq, Hkv, Sq, Skv, hd, causal, window,
                                      bq, bk, pairs):
    """The model-side XLA attention (both enumerations) equals the oracle."""
    q = _rand((B, Hq, Sq, hd), dtype)
    k = _rand((B, Hkv, Skv, hd), dtype)
    v = _rand((B, Hkv, Skv, hd), dtype)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              q_block=bq, kv_block=bk, pairs=pairs)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_pairs_equals_rectangle():
    """Band enumeration is numerically identical to the rectangle path."""
    q = _rand((1, 4, 256, 32), jnp.float32)
    k = _rand((1, 2, 256, 32), jnp.float32)
    v = _rand((1, 2, 256, 32), jnp.float32)
    a = blockwise_attention(q, k, v, causal=True, q_block=64, kv_block=64, pairs=False)
    b = blockwise_attention(q, k, v, causal=True, q_block=64, kv_block=64, pairs=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("valid", [1, 100, 384])
@pytest.mark.parametrize("window", [0, 64])
def test_decode_attention_sweep(dtype, valid, window):
    B, Hq, Hkv, S, hd = 2, 8, 2, 384, 64
    q = _rand((B, Hq, 1, hd), dtype)
    kc = _rand((B, Hkv, S, hd), dtype)
    vc = _rand((B, Hkv, S, hd), dtype)
    out = decode_attention(q, kc, vc, valid, window=window)
    want = ref.attention_ref(q, kc, vc, causal=False, valid_len=valid, window=0)
    if window:
        # oracle with window mask anchored at valid-1
        mask_lo = valid - 1 - window
        kv_pos = np.arange(S)
        keep = (kv_pos < valid) & (kv_pos > mask_lo)
        s = jnp.einsum("bhgqd,bhkd->bhgqk",
                       q.reshape(B, Hkv, Hq // Hkv, 1, hd).astype(jnp.float32) * hd**-0.5,
                       kc.astype(jnp.float32))
        s = jnp.where(jnp.asarray(keep)[None, None, None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        want = jnp.einsum("bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32)).reshape(B, Hq, 1, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,D", [(64, 128), (100, 96), (256, 512)])
def test_rmsnorm_sweep(dtype, rows, D):
    x = _rand((rows, D), dtype)
    g = _rand((D,), dtype)
    out = rms_norm(x, g)
    want = ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D,F", [(64, 64, 128), (100, 96, 224)])
def test_swiglu_sweep(dtype, N, D, F):
    x = _rand((N, D), dtype) * 0.5
    wg = _rand((D, F), dtype) * 0.1
    wu = _rand((D, F), dtype) * 0.1
    wd = _rand((F, D), dtype) * 0.1
    out = swiglu(x, wg, wu, wd)
    want = ref.swiglu_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("dqk,dv", [(16, 32), (32, 32)])
def test_mlstm_kernel_sweep(chunk, dqk, dv):
    """The model's chunked mLSTM scan equals the sequential recurrence."""
    B, H, S = 2, 2, 256
    q = _rand((B, H, S, dqk), jnp.float32)
    k = _rand((B, H, S, dqk), jnp.float32)
    v = _rand((B, H, S, dv), jnp.float32)
    i_raw = _rand((B, H, S), jnp.float32)
    log_f = jnp.asarray(np.log(RNG.uniform(0.7, 1.0, (B, H, S))), jnp.float32)
    out = mlstm_chunked(q, k, v, i_raw, log_f, chunk=chunk)
    want = ref.mlstm_ref(q, k, v, i_raw, log_f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)
