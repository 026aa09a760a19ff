"""The attention dispatcher (``models/layers.py`` ``attention``): which path
each call is lowered to, and the Pallas flash kernel against the XLA path.

The kernel runs here under ``pltpu.force_tpu_interpret_mode``.  The path
choice is read from programs lowered for a TPU without one: lowering needs
no chip, and it is where ``lax.platform_dependent`` picks its branch.  The
cases on a mesh of four devices run in a subprocess that gives the CPU
backend four (the test process keeps one).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import ARCHS
from repro.launch.mesh import make_test_mesh
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
    # a model on a mesh (here of the one device) takes the kernel under shard_map
    "partitioned": (FITS, {"mesh": "one_device"}, "tpu", "pallas_flash"),
    "seq_128": ({n: (1, 2, 128, 64) for n in "qkv"}, {}, "tpu",
                "pallas_flash" if L.FLASH_MIN_SEQ <= 128 else "xla_blockwise"),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_dispatch_rule(case):
    shapes, kwargs, platform, path = DISPATCH_CASES[case]
    q, k, v = (jax.ShapeDtypeStruct(shapes[n], jnp.bfloat16) for n in "qkv")
    if kwargs.get("mesh") == "one_device":
        kwargs = dict(kwargs, mesh=make_test_mesh(data=1, model=1, devices=jax.devices()[:1]))
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


# ------------------------------------------------------------ on a mesh of 4
_ON_FOUR = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS, ShapeConfig
    from repro.launch.mesh import make_test_mesh
    from repro.launch.sharded_step import build_sharded_step
    from repro.models import layers as L

    out = {}

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    # the shard-mapped kernel against the XLA path: rows over data, heads over model
    mesh = make_test_mesh(data=2, model=2)
    q, k, v, do = (jax.random.normal(jax.random.PRNGKey(i), (2, 4, 256, 64), jnp.bfloat16)
                   for i in range(4))
    q, k, v = (jax.device_put(x, NamedSharding(mesh, P("data", "model"))) for x in (q, k, v))

    def value_and_grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * do.astype(jnp.float32))
        return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    with pltpu.force_tpu_interpret_mode():
        out_k, grads_k = value_and_grads(lambda q, k, v: L.sharded_flash_attention(q, k, v, mesh))
    out_x, grads_x = value_and_grads(lambda q, k, v: L.blockwise_attention(q, k, v, causal=True))
    out["kernel"] = {"out": rel(out_k, out_x), "grads": [rel(a, b) for a, b in zip(grads_k, grads_x)],
                     "spec": str(out_k.sharding.spec)}

    # the rule on a mesh, lowered for a TPU
    def lowered(fn, *args):
        before = L.attention_path_tally()
        text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        after = L.attention_path_tally()
        return {p: after[p] - before[p] for p in L.ATTENTION_PATHS}, text.count("tpu_custom_call")

    def rule(shape, data, model):
        m = make_test_mesh(data=data, model=model)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        grad = jax.grad(lambda q, k, v: jnp.sum(L.attention(q, k, v, mesh=m).astype(jnp.float32)),
                        argnums=(0, 1, 2))
        return lowered(grad, x, x, x)[0]

    out["rule"] = {
        "batch_split": rule((4, 2, 512, 64), 4, 1),
        "batch_and_heads": rule((2, 4, 512, 64), 2, 2),
        "heads_not_dividing": rule((2, 3, 512, 64), 2, 2),
        "batch_not_dividing": rule((2, 2, 512, 64), 4, 1),
    }

    # the ZeRO step of the trainer on (data=4, model=1): kernel, residuals saved
    st = build_sharded_step(ARCHS["qwen1.5-0.5b"].smoke(), ShapeConfig("t", 512, 8, "train"),
                            make_test_mesh(data=4, model=1))
    counts, mosaic = lowered(st.jitted, *st.args)
    out["step"] = {"counts": counts, "mosaic": mosaic}
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def on_four():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _ON_FOUR], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_kernel_matches_blockwise(on_four):
    """Each device's kernel on its own rows and heads, forward and q, k, v
    gradients, within the bf16 gap the one-device kernel test allows."""
    got = on_four["kernel"]
    assert got["spec"].startswith("PartitionSpec('data', 'model'")
    assert got["out"] < 1e-2
    assert all(g < 1e-2 for g in got["grads"]), got["grads"]


@pytest.mark.parametrize("case, path", [
    ("batch_split", "pallas_flash"),
    ("batch_and_heads", "pallas_flash"),
    ("heads_not_dividing", "xla_blockwise"),
    ("batch_not_dividing", "xla_blockwise"),
])
def test_dispatch_rule_on_a_mesh(on_four, case, path):
    counts = on_four["rule"][case]
    assert counts[path] >= 1 and sum(counts.values()) == counts[path], counts


def test_zero_step_takes_the_kernel_and_saves_its_residuals(on_four):
    """The sharded step that ``--mesh data=4,model=1`` trains with: one kernel
    call forward and one fused backward, as on one device."""
    step = on_four["step"]
    assert step["counts"] == {"pallas_flash": 1, "xla_blockwise": 0}
    assert step["mosaic"] == 2
