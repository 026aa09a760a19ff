"""Elastic scaling + explicit DCN grad sync (subprocess, 8 virtual devices)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_ELASTIC = textwrap.dedent(
    """
    import json, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS
    from repro.models import build_model, params as PM
    from repro.train import AdamWConfig, CheckpointManager, init_opt_state
    from repro.launch.mesh import make_test_mesh

    cfg = ARCHS["qwen1.5-0.5b"].smoke()
    # train on a 2x4 mesh, checkpoint, restore onto 4x2 AND onto 1 device
    mesh_a = make_test_mesh(data=2, model=4)
    model = build_model(cfg, mesh=mesh_a, model_axis=4)
    layout = model.layout()
    sh_a = jax.tree.map(lambda s: NamedSharding(mesh_a, s), PM.specs(layout),
                        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(PM.materialize(layout, jax.random.PRNGKey(0), cfg.dtype), sh_a)
    opt = init_opt_state(params, AdamWConfig())

    ckpt = CheckpointManager(tempfile.mkdtemp(), keep=1)
    ckpt.save(5, params, opt, mesh_shape={"data": 2, "model": 4}, blocking=True)

    # elastic restore: different mesh factorisation
    mesh_b = make_test_mesh(data=4, model=2)
    model_b = build_model(cfg, mesh=mesh_b, model_axis=2)
    layout_b = model_b.layout()
    sh_b = jax.tree.map(lambda s: NamedSharding(mesh_b, s), PM.specs(layout_b),
                        is_leaf=lambda x: isinstance(x, P))
    step, p2, o2, _ = ckpt.restore(
        template={"params": params, "opt": opt},
        shardings={"params": sh_b, "opt": jax.tree.map(lambda _: NamedSharding(mesh_b, P()), opt)},
    )
    ok_b = all(
        bool(jnp.allclose(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    )

    # shrink-to-one-device restore (replacement-fleet scenario)
    step, p3, o3, _ = ckpt.restore(template={"params": params, "opt": opt})
    ok_c = all(
        bool(jnp.allclose(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p3))
    )
    print(json.dumps({"ok_resharded": ok_b, "ok_gathered": ok_c, "step": step}))
    """
)

_GRADSYNC = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from repro.train.sync import init_error_state, two_level_grad_sync

    mesh = make_test_mesh(data=2, model=2, pods=2)
    rng = np.random.default_rng(0)
    grads = {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    errors = init_error_state(grads)

    synced, new_err = two_level_grad_sync(grads, errors, mesh, compress=True)
    # replicated identical inputs -> pmean == identity up to int8 quantisation
    err = max(float(jnp.abs(synced[k] - grads[k]).max() /
                    (jnp.abs(grads[k]).max())) for k in grads)
    # error feedback captured the quantisation residual
    res = float(sum(jnp.abs(v).sum() for v in jax.tree.leaves(new_err)))
    print(json.dumps({"rel_err": err, "residual": res}))
    """
)


def _run(script: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Checkpoint from a 2x4 mesh restores onto 4x2 and onto 1 device."""
    out = _run(_ELASTIC)
    assert out["ok_resharded"] and out["ok_gathered"] and out["step"] == 5


@pytest.mark.slow
def test_two_level_grad_sync_int8():
    """Pod-axis int8 error-feedback sync: value preserved to quantisation
    accuracy, residual captured for the next step."""
    out = _run(_GRADSYNC)
    assert out["rel_err"] < 0.02
    assert out["residual"] > 0
