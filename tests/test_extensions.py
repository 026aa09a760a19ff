"""Beyond-paper extensions: the chunked SSD scan + sequence-parallel flash decoding."""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.hymba import ssd_scan

RNG = np.random.default_rng(7)


@pytest.mark.parametrize(
    "S,N,chd,chunk",
    [
        (64, 4, 8, 16),
        (128, 8, 16, 32),
        (128, 16, 32, 32),
        (128, 8, 16, 64),
        (128, 16, 32, 64),
    ],
)
def test_ssd_scan_matches_sequential_recurrence(S, N, chd, chunk):
    """Chunked SSD == step-by-step h_t = a_t h + b_t x_t^T; y_t = c_t.h_t."""
    B, H = 1, 2
    lf = jnp.asarray(np.log(RNG.uniform(0.6, 1.0, (B, S, H))), jnp.float32)
    b_in = jnp.asarray(RNG.normal(size=(B, S, H, N)), jnp.float32)
    x_in = jnp.asarray(RNG.normal(size=(B, S, H, chd)), jnp.float32)
    c_out = jnp.asarray(RNG.normal(size=(B, S, H, N)), jnp.float32)

    h = np.zeros((B, H, chd, N), np.float64)
    want = np.zeros((B, S, H, chd), np.float64)
    for t in range(S):
        a = np.exp(np.asarray(lf[:, t], np.float64))[..., None, None]
        outer = np.asarray(x_in[:, t], np.float64)[..., None] * np.asarray(
            b_in[:, t], np.float64
        )[..., None, :]
        h = a * h + outer
        want[:, t] = np.einsum("bhcn,bhn->bhc", h, np.asarray(c_out[:, t], np.float64))

    got, h_last = ssd_scan(lf, b_in, x_in, c_out, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_last), h, rtol=1e-4, atol=1e-4)


_FLASH_DECODE = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.serve.flash_decoding import make_flash_decode
    from repro.models.ref import decode_attention_ref

    mesh = make_test_mesh(data=2, model=4)
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, hd = 2, 10, 2, 256, 32          # 10 heads: indivisible by 4!
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Hkv, S, hd)), jnp.float32)
    kc = jax.device_put(kc, NamedSharding(mesh, P(None, None, "model", None)))
    vc = jax.device_put(vc, NamedSharding(mesh, P(None, None, "model", None)))

    fn = jax.jit(make_flash_decode(mesh))
    errs = []
    for valid in (1, 130, 256):
        out = fn(q, kc, vc, jnp.asarray(valid))
        want = decode_attention_ref(q, kc, vc, valid)
        errs.append(float(jnp.abs(out - want).max()))
    print(json.dumps({"max_err": max(errs)}))
    """
)


@pytest.mark.slow
def test_flash_decoding_sequence_parallel():
    """shard_map partial-softmax merge == full-softmax oracle, with a head
    count (10) that cannot shard the 4-way model axis."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _FLASH_DECODE],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["max_err"] < 2e-5
