"""The attention dispatcher (``models/layers.py`` ``attention``): which path
each call is lowered to, and the Pallas flash kernel against the XLA path.

The kernel runs here under ``pltpu.force_tpu_interpret_mode``.  The path
choice is read from programs lowered for a TPU without one: lowering needs
no chip, and it is where ``lax.platform_dependent`` picks its branch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import ARCHS
from repro.models import build_model, params as PM
from repro.models import layers as L


def _rand(shape, seed, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("S", [128, 256])
def test_kernel_matches_blockwise(S):
    B, H, hd = 1, 2, 64
    q, k, v, do = (_rand((B, H, S, hd), i) for i in range(4))

    def value_and_grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32))
        return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    with pltpu.force_tpu_interpret_mode():
        out_k, grads_k = value_and_grads(L.flash_causal_attention)
    out_x, grads_x = value_and_grads(lambda q, k, v: L.blockwise_attention(q, k, v, causal=True))
    assert out_k.dtype == out_x.dtype == jnp.bfloat16
    # bf16 inputs and outputs, f32 statistics on both paths: a few bf16 ulps apart
    assert _rel(out_k, out_x) < 1e-2
    for name, gk, gx in zip("qkv", grads_k, grads_x):
        assert gk.dtype == jnp.bfloat16
        assert _rel(gk, gx) < 1e-2, name


def _lowered(fn, *args, platform: str):
    """Per-path attention calls in ``fn`` lowered for ``platform``, and how
    many Mosaic kernel calls the program holds."""
    before = L.attention_path_tally()
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,)).as_text()
    after = L.attention_path_tally()
    return {p: after[p] - before[p] for p in L.ATTENTION_PATHS}, text.count("tpu_custom_call")


def _grad_of(attn):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))


S_OK = max(256, L.FLASH_MIN_SEQ)
FITS = dict(q=(1, 2, S_OK, 64), k=(1, 2, S_OK, 64), v=(1, 2, S_OK, 64))
DISPATCH_CASES = {
    # name: (shapes, attention kwargs, lowering platform, path)
    "fits": (FITS, {}, "tpu", "pallas_flash"),
    "cpu_platform": (FITS, {}, "cpu", "xla_blockwise"),
    "not_causal": (FITS, {"causal": False}, "tpu", "xla_blockwise"),
    "window": (FITS, {"window": 64}, "tpu", "xla_blockwise"),
    "q_offset": (FITS, {"q_offset": 128}, "tpu", "xla_blockwise"),
    "gqa": (dict(FITS, q=(1, 4, S_OK, 64)), {}, "tpu", "xla_blockwise"),
    "v_width": (dict(FITS, v=(1, 2, S_OK, 32)), {}, "tpu", "xla_blockwise"),
    "ragged_seq": ({n: (1, 2, 200, 64) for n in "qkv"}, {}, "tpu", "xla_blockwise"),
    "head_192": ({n: (1, 2, S_OK, 192) for n in "qkv"}, {}, "tpu", "xla_blockwise"),
    "partitioned": (FITS, {"partitioned": True}, "tpu", "xla_blockwise"),
    "seq_128": ({n: (1, 2, 128, 64) for n in "qkv"}, {}, "tpu",
                "pallas_flash" if L.FLASH_MIN_SEQ <= 128 else "xla_blockwise"),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_dispatch_rule(case):
    shapes, kwargs, platform, path = DISPATCH_CASES[case]
    q, k, v = (jax.ShapeDtypeStruct(shapes[n], jnp.bfloat16) for n in "qkv")
    attn = lambda q, k, v: L.attention(q, k, v, q_block=64, kv_block=64, **kwargs)
    counts, mosaic = _lowered(_grad_of(attn), q, k, v, platform=platform)
    assert counts[path] >= 1
    assert sum(counts.values()) == counts[path]
    assert (mosaic > 0) == (path == "pallas_flash")


@pytest.mark.parametrize("platform, path", [("tpu", "pallas_flash"), ("cpu", "xla_blockwise")])
def test_decoder_routes_attention_through_the_dispatcher(platform, path):
    """qwen1.5's decoder (16 heads of 16 kv heads, no window) takes the kernel
    on a TPU and the XLA path elsewhere, in the differentiated layer scan.
    On a TPU the step holds two kernel calls, the forward and the fused
    backward: the remat policy keeps the kernel's residuals, so the forward
    does not rerun in the backward pass."""
    cfg = ARCHS["qwen1.5-0.5b"].smoke()
    model = build_model(cfg, mesh=None)
    tokens = jax.ShapeDtypeStruct((1, S_OK), jnp.int32)
    params = PM.abstract(model.layout(), cfg.dtype)
    grad = jax.grad(lambda p, b: model.loss(p, b)[0])
    counts, mosaic = _lowered(grad, params, {"tokens": tokens, "labels": tokens},
                              platform=platform)
    assert counts[path] >= 1 and sum(counts.values()) == counts[path]
    assert mosaic == (2 if platform == "tpu" else 0)
