"""The trainer's steps at full qwen1.5-0.5b width, compiled for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without the chip, refuses
what the chip would refuse, and ``memory_analysis()`` gives the bytes each
device must hold.  The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library, and every
test worker imports this file.  ``chip_smoke.py`` runs the same steps on the
chip.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS, ShapeConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.sharded_step import abstract_opt_state, build_sharded_step
from repro.models import build_model, params as PM
from repro.models.layers import attention_path_tally
from repro.train import AdamWConfig, make_train_step

ROOT = Path(__file__).resolve().parents[1]
CFG = ARCHS["qwen1.5-0.5b"]
HBM_BYTES = 16 * 2**30           # one TPU v5e chip
BATCH, SEQ = 8, 512              # chip_smoke's one-chip run
FOUR_CHIP_BATCH = 32             # a global batch of 8 per chip
DP4_BATCH, DP4_SEQ = 8, 2048     # the four-chip benchmark cell, qwen05b-dp4-seq2048


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off: a
    compile for a described chip is written to it but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip_memory(topo):
    """The trainer's jitted step (``launch/train.py``) on one described chip."""
    model = build_model(CFG, mesh=None)
    opt_cfg = AdamWConfig()
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    args = (
        on_chip(PM.abstract(model.layout(), CFG.dtype)),
        on_chip(abstract_opt_state(model.layout(), opt_cfg)),
        on_chip({"tokens": tokens, "labels": tokens}),
    )
    step = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
    return step.lower(*args).compile().memory_analysis()


def test_full_width_train_step_fits_one_chip(one_chip_memory):
    mem = one_chip_memory
    assert mem.argument_size_in_bytes > 6e9          # bf16 params + fp32 AdamW states
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_data_parallel_step_compiles_on_four_chips(topo, one_chip_memory):
    mesh = make_test_mesh(data=4, model=1, devices=topo.devices)
    step = build_sharded_step(CFG, ShapeConfig("dp4", SEQ, FOUR_CHIP_BATCH, "train"), mesh)
    compiled = step.jitted.lower(*step.args).compile()
    mem = compiled.memory_analysis()                # per device
    assert mem.argument_size_in_bytes < one_chip_memory.argument_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo    # gradients cross chips


def test_data_parallel_step_takes_the_kernel_on_four_chips(topo):
    """``train.py --mesh data=4,model=1`` at the four-chip cell's 8 x 2048:
    attention runs the Pallas kernel under shard_map, two rows per chip, and
    the step fits one chip's memory."""
    mesh = make_test_mesh(data=4, model=1, devices=topo.devices)
    step = build_sharded_step(CFG, ShapeConfig("dp4", DP4_SEQ, DP4_BATCH, "train"), mesh)
    before = attention_path_tally()
    lowered = step.jitted.lower(*step.args)
    paths = {p: n - before[p] for p, n in attention_path_tally().items()}
    assert paths == {"pallas_flash": 1, "xla_blockwise": 0}
    compiled = lowered.compile()
    mem = compiled.memory_analysis()                # per device
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert compiled.as_text().count("tpu_custom_call") >= 2    # forward and fused backward


def test_chip_smoke_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    lines = run.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1]
    assert "needs a TPU" in run.stderr
