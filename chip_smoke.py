"""Bring-up smoke for the Hoard-fed trainer on a TPU v5e.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the four-chip data-parallel phase only

One chip (default): runs the trainer's entry point, ``repro.launch.train.main``,
on full-width qwen1.5-0.5b at batch 8 x seq 512 for 6 steps, fed from real
stripe files in the Hoard store, then

* fails if the trainer restarted even once;
* restores the final checkpoint with the trainer's template and checks every
  leaf's dtype and shape against the manifest, and the bf16 params bit for bit;
* recomputes the step-0 loss on the CPU backend from the same seed-0 params and
  the loader's first batch: chip and CPU agree within a relative 2e-2.

It then checks one qwen-width layer's causal attention on the chip at the
benchmark's two shapes (2 x 2048 and 32 x 128): the Pallas flash kernel
against the XLA blockwise path, loss and gradients within a relative 2e-2,
with one timing line per shape (milliseconds of a forward and backward pass
of each path, host clock around ``block_until_ready``), and prints how many
attention calls each path has been lowered for.

Four chips: the trainer's entry point again, with ``--mesh data=4,model=1``
(ZeRO data parallelism: params replicated, AdamW's state sharded over the four
chips), at the same global batch of 8 x 512 for 6 steps.  It fails unless
attention was lowered to the Pallas kernel alone, every param and optimizer
leaf spans the four chips, some optimizer leaves are sharded and none on a
layer scan axis (it prints ``zero_placement_tally``: AdamW's state leaves
and bytes by where ZeRO put them), and it prints each chip's peak memory.
Then the same run on one chip: each step's loss within a relative 2e-2 of
the four chips'.

The script refuses to run anywhere but a TPU.  It prints what it checked,
the device's peak memory and the attention timing lines, never a step time;
its last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import ARCHS  # noqa: E402
from repro.data import TokenLoader  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model, params as PM  # noqa: E402
from repro.models.layers import (  # noqa: E402
    attention, attention_path_tally, blockwise_attention, flash_causal_attention,
)
from repro.train import (  # noqa: E402
    AdamWConfig, CheckpointManager, SamplerState, init_train_state, zero_placement_tally,
)

ARCH = "qwen1.5-0.5b"
FULL_WIDTH = True          # False only in CPU rehearsals of the phases
SEED = 0
BATCH, SEQ, STEPS = 8, 512, 6
ATTN_SHAPES = ((2, 2048), (32, 128))   # batch x seq of the benchmark's two cells
ATTN_REPEATS = 20
RTOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def close(a: float, b: float) -> bool:
    return math.isfinite(a) and math.isfinite(b) and abs(a - b) <= RTOL * abs(b)


class CompileLog:
    """Backend compile seconds (cache retrievals included) and cache hits."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> str:
        return (f"compile_s={self.seconds:.3f} persistent_cache_hits={self.hits} "
                f"persistent_cache_misses={self.misses}")


def model_config():
    return ARCHS[ARCH] if FULL_WIDTH else ARCHS[ARCH].smoke()


def first_batch(batch: int, work: Path) -> dict:
    """The first batch a fresh trainer's loader yields, as numpy."""
    store, dspec, reader = train.stripe_token_corpus(
        "train-corpus", model_config().vocab, batch=batch, seq=SEQ, seed=SEED,
        data_root=str(work),
    )
    it = iter(TokenLoader(store, dspec, reader, batch=batch, state=SamplerState(seed=SEED)))
    return dict(zip(("tokens", "labels"), next(it)))


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


# --------------------------------------------------------------- one chip
def one_chip(work: Path) -> None:
    cfg = model_config()
    model = build_model(cfg, mesh=None)
    print(f"arch={ARCH} params={PM.param_count(model.layout())} "
          f"batch x seq={BATCH} x {SEQ} dtype={cfg.dtype}")

    ckpt_dir = work / "ckpt"
    argv = ["--arch", ARCH, "--batch", str(BATCH), "--seq", str(SEQ),
            "--steps", str(STEPS), "--seed", str(SEED),
            "--ckpt-every", str(STEPS + 1), "--ckpt-dir", str(ckpt_dir),
            "--data-root", str(work / "stripes")]
    result = train.main(argv + (["--full-config"] if FULL_WIDTH else []))
    print(f"losses={result.losses}")
    print(f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")
    print(f"memory_stats={jax.devices()[0].memory_stats()}")
    check(result.restarts == 0, f"trainer restarted {result.restarts} time(s)")
    check(result.final_step == STEPS and len(result.losses) == STEPS,
          f"trainer stopped at step {result.final_step} with {len(result.losses)} losses")
    check(all(math.isfinite(l) for l in result.losses), "non-finite training loss")

    # ---- checkpoint: restore with the trainer's template ----------------
    opt_cfg = AdamWConfig()
    template = jax.eval_shape(lambda: dict(zip(
        ("params", "opt"), init_train_state(model, jax.random.PRNGKey(SEED), opt_cfg))))
    step, params, opt, _ = CheckpointManager(str(ckpt_dir)).restore(template=template)
    check(step == STEPS, f"latest checkpoint is step {step}, want {STEPS}")
    with open(ckpt_dir / f"step_{step:06d}" / "manifest.json") as fh:
        manifest = json.load(fh)
    restored = jax.tree.leaves({"params": params, "opt": opt})
    wanted = jax.tree.leaves(template)
    check(len(restored) == manifest["n_leaves"] == len(wanted), "checkpoint leaf count")
    for i, (got, want) in enumerate(zip(restored, wanted)):
        check(str(got.dtype) == manifest["leaf_dtypes"][i] == str(want.dtype),
              f"leaf {i}: dtype {got.dtype}, manifest {manifest['leaf_dtypes'][i]}, "
              f"template {want.dtype}")
        check(list(got.shape) == manifest["leaf_shapes"][i] == list(want.shape),
              f"leaf {i}: shape {got.shape}, manifest {manifest['leaf_shapes'][i]}")
    final = jax.device_get(result.params)
    n_bf16 = 0
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(final)):
        want = np.asarray(want)
        n_bf16 += str(want.dtype) == "bfloat16"
        check(got.dtype == want.dtype and got.tobytes() == want.tobytes(),
              "restored params differ from the trained ones")
    check(n_bf16 > 0 or not FULL_WIDTH, "full-width params hold no bf16 leaf")
    print(f"checkpoint: step {step}, {len(restored)} leaves match the manifest, "
          f"params bit-identical ({n_bf16} bf16 leaves)")
    loss_chip = result.losses[0]
    del result, final, params, opt, restored

    # ---- step-0 loss: chip (the trainer's) vs CPU backend ----------------
    cpu = jax.devices("cpu")[0]
    batch = first_batch(BATCH, work / "ref_stripes")
    p0 = jax.device_get(PM.materialize(model.layout(), jax.random.PRNGKey(SEED), cfg.dtype))
    with jax.default_device(cpu):
        loss_cpu, _ = jax.jit(model.loss)(jax.device_put(p0, cpu), jax.device_put(batch, cpu))
    loss_cpu = float(loss_cpu)
    print(f"reference step-0 loss: chip={loss_chip} cpu={loss_cpu}")
    check(close(loss_chip, loss_cpu), f"chip step-0 loss {loss_chip} vs CPU {loss_cpu}")
    attention_check()


# ------------------------------------------------------------- attention
def _attention_grads(fn):
    """jit of (loss, (dq, dk, dv)) of one attention call, loss = mean(o**2) in f32."""
    loss = lambda q, k, v: jnp.mean(jnp.square(fn(q, k, v).astype(jnp.float32)))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _ms_per_call(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(ATTN_REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / ATTN_REPEATS * 1e3


def attention_check() -> None:
    """One layer's causal attention: the Pallas kernel against the XLA path."""
    cfg = model_config()
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    xla = _attention_grads(lambda q, k, v: blockwise_attention(
        q, k, v, causal=True, q_block=cfg.q_block, kv_block=cfg.kv_block))
    kernel = _attention_grads(flash_causal_attention)
    dispatched = _attention_grads(lambda q, k, v: attention(q, k, v, causal=True))
    for B, S in ATTN_SHAPES:
        q, k, v = (jax.random.normal(key, (B, H, S, hd), jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(SEED), 3))
        (loss_k, grads_k), (loss_x, grads_x) = kernel(q, k, v), xla(q, k, v)
        norms_k = [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in grads_k]
        norms_x = [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in grads_x]
        gaps = [float(jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)))) / n
                for a, b, n in zip(grads_k, grads_x, norms_x)]
        print(f"attention {B} x {S}: loss kernel={float(loss_k)} xla={float(loss_x)}; "
              f"grad norms q/k/v kernel={norms_k} xla={norms_x}; relative gaps {gaps}")
        check(close(float(loss_k), float(loss_x)), f"attention {B} x {S}: loss")
        check(all(g <= RTOL for g in gaps), f"attention {B} x {S}: gradients {gaps}")
        before = attention_path_tally()
        jax.block_until_ready(dispatched(q, k, v))
        took = [p for p, n in attention_path_tally().items() if n > before[p]]
        print(f"attention {B} x {S} timing: kernel {_ms_per_call(kernel, q, k, v):.3f} ms, "
              f"xla {_ms_per_call(xla, q, k, v):.3f} ms per forward and backward; "
              f"the dispatcher takes {took}")
    print(f"attention paths lowered: {attention_path_tally()}")


# ------------------------------------------------------------- four chips
def four_chips(work: Path) -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    print(f"arch={ARCH} mesh=(data=4, model=1) global batch x seq={BATCH} x {SEQ}, "
          f"{STEPS} steps, against one chip at the same global batch")

    def trained(name: str, extra: list[str]) -> train.TrainResult:
        argv = ["--arch", ARCH, "--batch", str(BATCH), "--seq", str(SEQ),
                "--steps", str(STEPS), "--seed", str(SEED),
                "--ckpt-every", str(STEPS + 1), "--ckpt-dir", str(work / name / "ckpt"),
                "--data-root", str(work / name / "stripes")]
        result = train.main(argv + (["--full-config"] if FULL_WIDTH else []) + extra)
        check(result.restarts == 0, f"{name}: trainer restarted {result.restarts} time(s)")
        check(len(result.losses) == STEPS and all(math.isfinite(l) for l in result.losses),
              f"{name}: losses {result.losses}")
        return result

    before = attention_path_tally()
    four = trained("four", ["--mesh", "data=4,model=1"])
    lowered = {p: n - before[p] for p, n in attention_path_tally().items()}
    print(f"4 chips: losses={four.losses}; attention paths lowered {lowered}")
    if devices[0].platform == "tpu":
        check(lowered["pallas_flash"] > 0 and lowered["xla_blockwise"] == 0,
              f"the mesh step's attention was lowered to {lowered}, want the kernel alone")
    leaves = jax.tree.leaves((four.params, four.opt))
    spans = {len(x.sharding.device_set) for x in leaves}
    zero = sum(not x.sharding.is_fully_replicated for x in jax.tree.leaves(four.opt))
    print(f"{len(leaves)} param/opt leaves span {sorted(spans)} devices; "
          f"{zero} of {len(jax.tree.leaves(four.opt))} optimizer leaves are ZeRO-sharded")
    check(spans == {4}, f"param/opt leaves span {sorted(spans)} devices, want 4")
    check(zero > 0, "no optimizer leaf is sharded over the data axis")
    mesh = train.build_mesh({"data": 4, "model": 1})
    tally = zero_placement_tally(build_model(model_config(), mesh=mesh, model_axis=1).layout(),
                                 mesh, AdamWConfig())
    print(f"AdamW state by ZeRO placement, (leaves, bytes): {tally}")
    check(tally["scan_axis"][0] == 0, f"optimizer state sharded on a layer scan axis: {tally}")
    peaks = [peak_bytes(d) for d in devices[:4]]
    print(f"peak_bytes_in_use per device={peaks}")
    print(f"memory_stats[0]={devices[0].memory_stats()}")
    check(all(p > 0 for p in peaks), "a device reports no memory in use")
    losses4 = four.losses
    del four, leaves

    one = trained("one", [])
    print(f"1 chip, same global batch {BATCH}: losses={one.losses}")
    for step, (l4, l1) in enumerate(zip(losses4, one.losses)):
        check(close(l4, l1), f"step {step} loss: 4 chips {l4} vs 1 chip {l1}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel phase")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX's first device is {device.platform}")
    log = CompileLog()
    print(f"device={device.device_kind} count={len(jax.devices())} compile_cache={cache_dir}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        (four_chips if args.four_chips else one_chip)(Path(work))
    print(log.report())
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
