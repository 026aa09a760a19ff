"""Training substrate: optimizer, checkpointing, fault tolerance, data."""

import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.core import build_cluster
from repro.data import TokenDatasetSpec, TokenLoader, materialize_token_dataset
from repro.models import build_model
from repro.models import params as PM
from repro.train import (
    AdamWConfig,
    CheckpointManager,
    PreemptionGuard,
    SamplerState,
    StragglerMonitor,
    compress_int8,
    decompress_int8,
    init_train_state,
    make_train_step,
    opt_state_specs,
    run_with_restarts,
    zero_placement_tally,
    zero_spec_for,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = ARCHS["qwen1.5-0.5b"].smoke()
    model = build_model(cfg, mesh=None)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2)
    params, opt = init_train_state(model, KEY, opt_cfg)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32),
    }
    return cfg, model, opt_cfg, params, opt, batch


def test_loss_decreases_on_fixed_batch(tiny_setup):
    cfg, model, opt_cfg, params, opt, batch = tiny_setup
    step = jax.jit(make_train_step(model, opt_cfg))
    losses = []
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.05


def test_grad_clip_bounds_update(tiny_setup):
    cfg, model, opt_cfg, params, opt, batch = tiny_setup
    step = jax.jit(make_train_step(model, opt_cfg))
    _, _, m = step(params, opt, batch)
    assert float(m["grad_norm"]) > 0


def test_zero_spec_adds_data_axis():
    spec = zero_spec_for(P(None, "model"), (1024, 512), data_size=16)
    assert spec == P("data", "model")
    # already-sharded dim skipped, non-divisible dim skipped
    spec = zero_spec_for(P("model", None), (8, 30), data_size=16)
    assert spec == P("model", None)


DP4 = AbstractMesh((4, 1), ("data", "model"))     # the four-chip cell's mesh, described
SIZES = dict(DP4.shape)


@pytest.mark.parametrize("spec, shape, stacked, want", [
    # a stacked leaf: the first unsharded, divisible dimension after the scan axis
    (P(None, None, "model"), (24, 1024, 2816), 1, P(None, "data", "model")),
    (P(None, "model", None), (24, 2816, 1024), 1, P(None, "model", "data")),
    (P(None, None), (24, 1024), 1, P(None, "data")),
    (P(None, None, None), (2, 4, 64), 2, P(None, None, "data")),    # a scan of scans
    # none unsharded: after the axes that already hold a dimension
    (P(None, "model"), (24, 1024), 1, P(None, ("model", "data"))),
    # no other dimension divides: back to the scan axis
    (P(None, None), (24, 6), 1, P("data", None)),
    (P(None, "model"), (24, 6), 1, P("data", "model")),
    # an unstacked leaf: the first unsharded, divisible dimension, as before
    (P(None, "model"), (1024, 512), 0, P("data", "model")),
    (P("model", None), (8, 30), 0, P("model", None)),
    (P("model"), (1024,), 0, P("model")),
])
def test_zero_spec_keeps_state_off_the_scan_axis(spec, shape, stacked, want):
    assert zero_spec_for(spec, shape, 4, stacked=stacked, axis_sizes=SIZES) == want


def test_zero_specs_of_qwen_layers_leave_the_scan_axis():
    """At data=4, no layer-stacked leaf of qwen1.5-0.5b has its AdamW state
    sharded on axis 0 (the layer scan's), and the tally says so."""
    layout = build_model(ARCHS["qwen1.5-0.5b"], mesh=None, model_axis=1).layout()
    specs = opt_state_specs(layout, DP4, AdamWConfig())
    for state in ("mu", "nu", "master"):
        layers = jax.tree.leaves(specs[state]["layers"], is_leaf=lambda x: isinstance(x, P))
        assert len(layers) == 12
        assert all(s[0] is None for s in layers), layers
    n_bytes = 3 * 4 * PM.param_count(layout)
    assert zero_placement_tally(layout, DP4, AdamWConfig()) == {
        "scan_axis": (0, 0), "inner_axis": (42, n_bytes), "replicated": (0, 0)}


@pytest.mark.parametrize("mesh, master, want", [
    (DP4, True, {"scan_axis": (3, 288), "inner_axis": (6, 8448), "replicated": (3, 180)}),
    (DP4, False, {"scan_axis": (2, 192), "inner_axis": (4, 5632), "replicated": (2, 120)}),
    (AbstractMesh((2, 1), ("data", "model")), True,
     {"scan_axis": (0, 0), "inner_axis": (9, 8736), "replicated": (3, 180)}),
    (None, True, {"scan_axis": (0, 0), "inner_axis": (0, 0), "replicated": (12, 8916)}),
])
def test_zero_placement_tally(mesh, master, want):
    """Leaves of mu, nu (and master) and their fp32 bytes, by where ZeRO put them."""
    layout = {
        "embed": PM.ParamInfo((64, 8)),                  # axis 0 divides
        "odd": PM.ParamInfo((3, 5)),                     # nothing divides 4 or 2
        "layers": PM.stack(4, {"w": PM.ParamInfo((8, 6)),      # axis 1 divides
                               "b": PM.ParamInfo((6,))}),     # only the scan axis does at 4
    }
    assert layout["layers"]["b"].stacked == 1
    assert zero_placement_tally(layout, mesh, AdamWConfig(master_fp32=master)) == want


def test_int8_error_feedback_roundtrip():
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    err = jnp.zeros_like(g)
    q, scale, err1 = compress_int8(g, err)
    deq = decompress_int8(q, scale)
    # single-shot error bounded by one quantisation step
    assert float(jnp.abs(deq - g).max()) <= float(scale) + 1e-9
    # error feedback: accumulated residual re-enters next round
    q2, scale2, err2 = compress_int8(g, err1)
    deq2 = decompress_int8(q2, scale2)
    two_step = (deq + deq2) / 2
    assert float(jnp.abs(two_step - g).mean()) < float(jnp.abs(deq - g).mean()) + 1e-6


def test_checkpoint_roundtrip_and_prune(tiny_setup, tmp_path):
    cfg, model, opt_cfg, params, opt, batch = tiny_setup
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3):
        ckpt.save(step, params, opt, sampler=SamplerState(epoch=1, step_in_epoch=step),
                  blocking=True)
    assert ckpt.latest_step() == 3
    assert not os.path.exists(os.path.join(str(tmp_path), "step_000001"))
    s, p2, o2, sam = ckpt.restore(template={"params": params, "opt": opt})
    assert s == 3 and sam.step_in_epoch == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_restores_recorded_dtypes_bit_exact(tmp_path):
    """bf16 leaves (saved by np.save as raw void) come back as bf16, bit for
    bit, next to fp32 and int32 leaves."""
    rng = np.random.default_rng(5)
    params = {
        "w": jnp.asarray(rng.normal(size=(7, 3)), jnp.bfloat16),
        "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32),
    }
    opt = {"mu": jnp.asarray(rng.normal(size=(7, 3)), jnp.float32),
           "count": jnp.asarray(11, jnp.int32)}
    ckpt = CheckpointManager(str(tmp_path), keep=1)
    ckpt.save(4, params, opt, blocking=True)
    _, p2, o2, _ = ckpt.restore(template={"params": params, "opt": opt})
    for a, b in zip(jax.tree.leaves((params, opt)), jax.tree.leaves((p2, o2))):
        a = np.asarray(a)
        assert b.dtype == a.dtype and b.shape == a.shape
        assert b.tobytes() == a.tobytes()


def test_checkpoint_async_write(tiny_setup, tmp_path):
    cfg, model, opt_cfg, params, opt, batch = tiny_setup
    ckpt = CheckpointManager(str(tmp_path), keep=2, async_write=True)
    ckpt.save(7, params, opt)
    ckpt.wait()
    assert ckpt.latest_step() == 7


def test_torn_checkpoint_invisible(tiny_setup, tmp_path):
    """A crash mid-write leaves no committed step behind."""
    cfg, model, opt_cfg, params, opt, batch = tiny_setup
    ckpt = CheckpointManager(str(tmp_path), keep=3)
    ckpt.save(1, params, opt, blocking=True)
    torn = os.path.join(str(tmp_path), "step_000002")
    os.makedirs(torn)                      # no _COMMITTED marker
    assert ckpt.latest_step() == 1


def test_preemption_guard_flags_stop():
    with PreemptionGuard(signals=(signal.SIGUSR1,)) as guard:
        assert not guard.should_stop
        os.kill(os.getpid(), signal.SIGUSR1)
        assert guard.should_stop


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(window=20, threshold=3.0, min_samples=5)
    for _ in range(15):
        assert not mon.record(0.10 + np.random.default_rng(1).normal() * 0.001)
    assert mon.record(0.50)
    assert mon.flagged


def test_run_with_restarts_recovers():
    calls = []

    def loop(resume):
        calls.append(resume)
        if len(calls) < 3:
            raise RuntimeError("boom")
        return 99

    assert run_with_restarts(loop) == 99
    assert calls == [None, -1, -1]


def test_token_loader_resumable_deterministic(tmp_path):
    clock, topo, store, cache, engine = build_cluster()
    store.root = str(tmp_path)
    spec = TokenDatasetSpec("ds", n_sequences=32, seq_len=16, vocab=100)
    materialize_token_dataset(store, cache, spec, topo.nodes[:4], items_per_chunk=4)

    full = TokenLoader(store, spec, topo.nodes[0], batch=4)
    it = iter(full)
    seen = [next(it)[0] for _ in range(6)]

    resumed = TokenLoader(store, spec, topo.nodes[0], batch=4,
                          state=SamplerState(epoch=0, step_in_epoch=3, seed=spec.seed))
    it2 = iter(resumed)
    again = [next(it2)[0] for _ in range(3)]
    for a, b in zip(seen[3:], again):
        np.testing.assert_array_equal(a, b)


def test_trainer_main_reports_losses_restarts_and_final_params(tmp_path, monkeypatch):
    """The entry point returns what its caller needs to check a run: one
    finite loss per step, zero restarts, and the params the last checkpoint
    holds (bit for bit)."""
    from repro.launch import train

    # a set variable leaves JAX's cache config alone: no .jax_cache from tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    result = train.main([
        "--steps", "3", "--batch", "2", "--seq", "32", "--ckpt-every", "4",
        "--ckpt-dir", str(tmp_path / "ckpt"), "--data-root", str(tmp_path / "data"),
    ])
    assert result.final_step == 3 and result.restarts == 0
    assert len(result.losses) == 3 and all(np.isfinite(result.losses))
    model = build_model(ARCHS["qwen1.5-0.5b"].smoke(), mesh=None)
    _, opt = init_train_state(model, KEY, AdamWConfig())
    step, params, _, _ = CheckpointManager(str(tmp_path / "ckpt")).restore(
        template={"params": result.params, "opt": opt})
    assert step == 3
    for a, b in zip(jax.tree.leaves(result.params), jax.tree.leaves(params)):
        assert np.asarray(a).tobytes() == b.tobytes()


# ------------------------------------------- the trainer on a mesh of four
_MESH_TRAIN = textwrap.dedent(
    """
    import json, sys, tempfile
    import jax
    from repro.launch import train

    work = tempfile.mkdtemp()
    common = ["--steps", "6", "--batch", "8", "--seq", "64", "--ckpt-every", "2",
              "--data-root", work + "/data"]
    mesh = ["--mesh", "data=4,model=1"]
    one = train.main(common + ["--ckpt-dir", work + "/one"])
    four = train.main(common + mesh + ["--ckpt-dir", work + "/four"])

    # a read that fails once, at step 5: the trainer restores the latest
    # checkpoint onto the mesh and trains on from there
    Real = train.TokenLoader
    reads = [0]

    class FailsOnce(Real):
        def __iter__(self):
            for batch in super().__iter__():
                reads[0] += 1
                if reads[0] == 6:
                    raise OSError("stripe read failed")
                yield batch

    train.TokenLoader = FailsOnce
    resumed = train.main(common + mesh + ["--ckpt-dir", work + "/resumed"])

    def placed(r):
        leaves = jax.tree.leaves((r.params, r.opt))
        return {"devices": sorted({len(x.sharding.device_set) for x in leaves}),
                "zero": sum(not x.sharding.is_fully_replicated for x in jax.tree.leaves(r.opt))}

    print(json.dumps({"one": one.losses, "four": four.losses, "resumed": resumed.losses,
                      "restarts": resumed.restarts, "four_placed": placed(four),
                      "resumed_placed": placed(resumed)}))
    """
)


@pytest.fixture(scope="module")
def mesh_training(tmp_path_factory):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"),
               # a set variable leaves JAX's cache config alone: no .jax_cache from tests
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jax_cache")))
    proc = subprocess.run([sys.executable, "-c", _MESH_TRAIN], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {**json.loads(proc.stdout.strip().splitlines()[-1]), "stdout": proc.stdout}


def test_trainer_on_a_mesh_matches_one_device(mesh_training):
    """``--mesh data=4,model=1`` at the same global batch trains the same
    model: float32 losses within 1e-5 relative, the room that summing the
    gradient over four shards in another order leaves."""
    one, four = mesh_training["one"], mesh_training["four"]
    assert len(one) == len(four) == 6
    np.testing.assert_allclose(four, one, rtol=1e-5)
    assert mesh_training["four_placed"]["devices"] == [4]
    assert mesh_training["four_placed"]["zero"] > 0


def test_trainer_on_a_mesh_prints_where_zero_put_the_state(mesh_training):
    """The first step's line under ``--mesh`` ends with the optimizer-state
    tally (leaves/bytes per placement); without a mesh it has none."""
    firsts = [l for l in mesh_training["stdout"].splitlines() if l.startswith("step     0 ")]
    assert len(firsts) == 3                  # one device, four, four until the failed read
    layout = build_model(ARCHS["qwen1.5-0.5b"].smoke(), mesh=None, model_axis=1).layout()
    tally = zero_placement_tally(layout, DP4, AdamWConfig())
    want = " zero " + " ".join(f"{k}={n}/{b}B" for k, (n, b) in tally.items())
    assert " zero " not in firsts[0]
    assert firsts[1].endswith(want) and firsts[2].endswith(want)
    assert " zero scan_axis=0/0B inner_axis=" in want


def test_trainer_on_a_mesh_resumes_from_its_checkpoint(mesh_training):
    """A failed read restarts the trainer, which restores the last committed
    checkpoint with the step's shardings and continues the same losses."""
    resumed, four = mesh_training["resumed"], mesh_training["four"]
    assert mesh_training["restarts"] == 1
    assert 0 < len(resumed) < len(four)
    np.testing.assert_allclose(resumed, four[-len(resumed):], rtol=1e-6)
    assert mesh_training["resumed_placed"] == mesh_training["four_placed"]


@pytest.mark.parametrize("text, shape", [
    ("data=4,model=1", {"data": 4, "model": 1}),
    ("model=2,data=2", {"data": 2, "model": 2}),
    ("data=8", {"data": 8}),
])
def test_parse_mesh(text, shape):
    from repro.launch.train import parse_mesh
    assert parse_mesh(text) == shape


@pytest.mark.parametrize("text", ["data=0", "pod=2,data=2", "data=4,model", "data=x"])
def test_parse_mesh_refuses(text):
    from repro.launch.train import parse_mesh
    with pytest.raises(ValueError):
        parse_mesh(text)
