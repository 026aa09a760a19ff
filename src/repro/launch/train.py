"""End-to-end training driver: Hoard-cached data -> sharded train loop.

Wires every substrate together:

* builds the cluster model (topology + stripe store + cache + placement),
* materialises the seeded token corpus as real stripe files in the Hoard
  cache,
* runs the jitted train step with async checkpoints, preemption guard,
  straggler monitor and crash-restart: on the default device, or with
  ``--mesh`` on a mesh of this host's devices.

``--mesh data=4,model=1`` (axes ``data`` and ``model``) builds the step
with ``launch/sharded_step.py``'s ``build_sharded_step``, as the dry-run and
the benchmark do: parameters laid out by the model's specs
(replicated where ``model`` is 1), AdamW's state ZeRO-sharded over ``data``,
and each batch placed by the step's batch sharding in one ``device_put``, so
that each device receives only its own rows.  ``--batch`` is the global
batch.  Checkpoints restore onto the same shardings.

``main`` returns a :class:`TrainResult` (per-step losses, restart count, the
final parameters and optimizer state) so that a caller such as
``chip_smoke.py`` can check the run.  Every tenth step it prints the loss,
the step time (the interval between two loss fetches, which wait for the
device, over the steps between them) and, from :mod:`repro.core.hostspans`,
the read path's locate, io and verify milliseconds per step, its read
amplification and its replica fallbacks (a chunk replica that failed its CRC
or is gone, read again from a healthy copy).  The straggler monitor watches each batch's ``loader.batch``
time: a slow or failing stripe read is Hoard's straggler.

The step's ops carry the named scopes ``embed``, ``attention``, ``mlp``,
``logits_loss`` and ``optimizer`` (the attention kernel, where it runs, in
``flash`` inside ``attention``), which XProf's op profile and trace viewer
group by.  JAX's persistent compile cache leaves op names out of its key, so
an executable cached before the scopes existed is loaded without them: clear
the cache directory (or set ``jax_compilation_cache_include_metadata_in_key``)
before profiling a step that was cached by an older build.  The first step's
line also prints how many attention calls were lowered to each path
(``pallas_flash``, ``xla_blockwise``; see ``models/layers.py`` ``attention``).

The smoke-sized model by default; ``--full-config`` for the architecture's
published widths.  Usage:

    python -m repro.launch.train --arch qwen1.5-0.5b --steps 50 \
        --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
    python -m repro.launch.train --full-config --mesh data=4,model=1 \
        --batch 8 --seq 2048 --steps 30
"""

from __future__ import annotations

import argparse
import os
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCHS, ShapeConfig
from ..core import Node, StripeStore, build_cluster, hostspans
from ..data import TokenDatasetSpec, TokenLoader, materialize_token_dataset
from ..models import build_model
from ..models import params as PM
from ..models.layers import attention_path_tally
from ..train import (
    AdamWConfig,
    CheckpointManager,
    PreemptionGuard,
    SamplerState,
    StragglerMonitor,
    config_digest,
    init_opt_state,
    init_train_state,
    make_train_step,
    run_with_restarts,
)
from .compile_cache import enable_compile_cache
from .mesh import make_test_mesh
from .sharded_step import build_sharded_step


@dataclass
class TrainResult:
    final_step: int
    restarts: int        # crash-restarts that run_with_restarts absorbed
    losses: list         # per-step losses of the attempt that finished
    params: Any          # final parameters, as the last checkpoint holds them
    opt: Any = None      # final optimizer state, placed as the step left it


def stripe_token_corpus(
    dataset_id: str, vocab: int, *, batch: int, seq: int, seed: int,
    data_root: Optional[str] = None,
) -> tuple[StripeStore, TokenDatasetSpec, Node]:
    """Stripe the seeded token corpus over 4 nodes as real chunk files.

    Returns the store, the dataset spec and the reading node: what a
    ``TokenLoader`` needs.  The bytes depend only on the arguments, so a second
    call with the same ones feeds a loader the same batches.
    """
    clock, topo, store, cache, engine = build_cluster()
    store.root = data_root or tempfile.mkdtemp(prefix="hoard_")
    dspec = TokenDatasetSpec(
        dataset_id, n_sequences=max(256, batch * 32), seq_len=seq, vocab=vocab, seed=seed,
    )
    materialize_token_dataset(store, cache, dspec, topo.nodes[:4], items_per_chunk=16)
    return store, dspec, topo.nodes[0]


def _timings(fetched: Optional[tuple[int, float]], step: int, now: float) -> str:
    """Step time since the last loss fetch, and the read path over those steps."""
    if fetched is None:
        return ""
    n = step - fetched[0]
    out = f" step {(now - fetched[1]) / n * 1e3:.1f}ms"
    recs = hostspans.last(n)
    if recs is not None:
        ms = [hostspans.per_batch_ms(recs, f"stripe.{s}") for s in ("locate", "io", "verify")]
        fallbacks = sum(r.counters.get("stripe.fallbacks", 0) for r in recs)
        out += (" read locate/io/verify {:.2f}/{:.2f}/{:.2f}ms".format(*ms)
                + f" amplification {hostspans.read_amplification(recs):.0f}x"
                + f" fallbacks {fallbacks}")
    return out


MESH_AXES = ("data", "model")


def parse_mesh(text: str) -> dict[str, int]:
    """``"data=4,model=1"`` -> ``{"data": 4, "model": 1}``."""
    shape = {}
    for part in text.split(","):
        axis, _, n = part.partition("=")
        if axis not in MESH_AXES or not n.isdigit() or int(n) < 1:
            raise ValueError(f"--mesh {text!r}: want axis=size pairs over {MESH_AXES}")
        shape[axis] = int(n)
    return shape


def build_mesh(shape: dict[str, int]):
    """A (data, model) mesh of this host's first devices."""
    data, model = shape.get("data", 1), shape.get("model", 1)
    devices = jax.devices()
    if len(devices) < data * model:
        raise ValueError(f"--mesh {shape} needs {data * model} devices, found {len(devices)}")
    return make_test_mesh(data=data, model=model, devices=devices[:data * model])


def main(argv=None) -> TrainResult:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dataset-id", default="train-corpus")
    ap.add_argument("--data-root", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (default: smoke config)")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    help="train data-parallel on a mesh of this host's devices, "
                         "e.g. data=4,model=1 (default: the default device alone)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch] if args.full_config else ARCHS[args.arch].smoke()
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=10)
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(), "repro_ckpt")
    ckpt = CheckpointManager(ckpt_dir, keep=3)

    # ---- Hoard data plane -------------------------------------------------
    store, dspec, reader = stripe_token_corpus(
        args.dataset_id, cfg.vocab, batch=args.batch, seq=args.seq, seed=args.seed,
        data_root=args.data_root,
    )
    print(f"[hoard] dataset {args.dataset_id!r} striped over 4 nodes "
          f"({dspec.n_sequences} seqs x {args.seq} tokens)")

    # ---- the step, its state and its batches: one device or a mesh --------
    if args.mesh is None:
        model = build_model(cfg, mesh=None)
        step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))

        def init_state(key):
            return init_train_state(model, key, opt_cfg)

        def put(toks, labels):
            return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    else:
        mesh = build_mesh(args.mesh)
        st = build_sharded_step(cfg, ShapeConfig("train", args.seq, args.batch, "train"), mesh,
                                opt_cfg)
        step_fn = st.jitted
        init_opt = jax.jit(lambda p: init_opt_state(p, opt_cfg), out_shardings=st.opt_sharding)
        print(f"[mesh] {dict(mesh.shape)}, global batch {args.batch}")

        def init_state(key):
            params = jax.device_put(PM.materialize(st.layout, key, cfg.dtype), st.param_sharding)
            return params, init_opt(params)

        def put(toks, labels):
            return jax.device_put({"tokens": toks, "labels": labels}, st.batch_sharding)
    digest = config_digest(cfg)

    def loop(resume) -> TrainResult:
        params, opt = init_state(jax.random.PRNGKey(args.seed))
        sampler = SamplerState(seed=args.seed)
        start = 0
        if resume is not None and ckpt.latest_step() is not None:
            state = {"params": params, "opt": opt}
            start, params, opt, sampler = ckpt.restore(
                template=state, shardings=jax.tree.map(lambda x: x.sharding, state))
            print(f"[restore] resumed from step {start}")
        loader = TokenLoader(store, dspec, reader, batch=args.batch, state=sampler)
        monitor = StragglerMonitor()
        losses = []
        it = iter(loader)

        fetched = None                  # (step, time) of the last loss fetch
        with PreemptionGuard() as guard:
            for step in range(start, args.steps):
                toks, labels = next(it)
                read_s = hostspans.last(1)[0].total_ns[hostspans.BATCH_SPAN] / 1e9
                if monitor.record(read_s):
                    print(f"[straggler] step {step} batch read took {read_s:.2f}s")
                params, opt, metrics = step_fn(params, opt, put(toks, labels))
                losses.append(metrics["loss"])
                if step % 10 == 0 or step == args.steps - 1:
                    line = (f"step {step:5d} loss={float(metrics['loss']):.4f} "
                            f"gnorm={float(metrics['grad_norm']):.3f}")
                    if step == start:
                        line += " attention " + " ".join(
                            f"{k}={n}" for k, n in attention_path_tally().items())
                    now = time.perf_counter()
                    print(line + _timings(fetched, step, now))
                    fetched = (step, now)
                if (step + 1) % args.ckpt_every == 0 or guard.should_stop:
                    ckpt.save(step + 1, params, opt, sampler=loader.state,
                              config_digest=digest, mesh_shape=args.mesh)
                if guard.should_stop:
                    print("[preempt] checkpointed and exiting")
                    break
        ckpt.save(args.steps, params, opt, sampler=loader.state,
                  config_digest=digest, mesh_shape=args.mesh, blocking=True)
        return TrainResult(args.steps, 0, [float(l) for l in losses], params, opt)

    restarts = []

    def on_restart(n, err):
        restarts.append(n)
        print(f"[restart {n}] {err}")

    result = run_with_restarts(loop, on_restart=on_restart)
    result.restarts = len(restarts)
    print(f"done at step {result.final_step}; checkpoints in {ckpt_dir}")
    return result


if __name__ == "__main__":
    main()
