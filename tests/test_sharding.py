"""Small-mesh sharding tests: run lower+compile in a subprocess with 8
virtual devices (the 512-device override belongs to the dry-run ONLY)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import json
    from repro.configs import ARCHS, ShapeConfig
    from repro.launch.mesh import make_test_mesh
    from repro.launch.sharded_step import build_sharded_step

    mesh = make_test_mesh(data=2, model=2, pods=2)
    step = build_sharded_step(ARCHS[%(arch)r].smoke(), ShapeConfig("t", 128, 8, %(kind)r), mesh)
    cost = step.jitted.lower(*step.args).compile().cost_analysis() or {}
    print(json.dumps({"ok": True, "flops": cost.get("flops", 0.0)}))
    """
)


def _run(arch: str, kind: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT % {"arch": arch, "kind": kind}],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"]


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mixtral-8x7b", "xlstm-1.3b"])
def test_multipod_mesh_train_compiles(arch):
    """(pod=2, data=2, model=2) mesh: train step lowers + compiles with the
    production sharding rules on reduced configs."""
    _run(arch, "train")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "hymba-1.5b"])
def test_multipod_mesh_decode_compiles(arch):
    _run(arch, "decode")
