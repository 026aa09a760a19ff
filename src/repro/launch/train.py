"""End-to-end training driver: Hoard-cached data -> sharded train loop.

Wires every substrate together:

* builds the cluster model (topology + stripe store + cache + placement),
* materialises the seeded token corpus as real stripe files in the Hoard
  cache,
* runs the jitted train step on the default device with async checkpoints,
  preemption guard, straggler monitor and crash-restart.

``main`` returns a :class:`TrainResult` (per-step losses, restart count and
the final parameters) so that a caller such as ``chip_smoke.py`` can check
the run.  The printed milliseconds are host time to read a batch and
dispatch the step, not device step time.

CPU-shaped by default (small mesh, smoke config); pass --full-config on a
real fleet.  Usage:

    python -m repro.launch.train --arch qwen1.5-0.5b --steps 50 \
        --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
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

from ..configs import ARCHS
from ..core import Node, StripeStore, build_cluster
from ..data import TokenDatasetSpec, TokenLoader, materialize_token_dataset
from ..models import build_model
from ..train import (
    AdamWConfig,
    CheckpointManager,
    PreemptionGuard,
    SamplerState,
    StragglerMonitor,
    config_digest,
    init_train_state,
    make_train_step,
    run_with_restarts,
)
from .compile_cache import enable_compile_cache


@dataclass
class TrainResult:
    final_step: int
    restarts: int        # crash-restarts that run_with_restarts absorbed
    losses: list         # per-step losses of the attempt that finished
    params: Any          # final parameters, as the last checkpoint holds them


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


def main(argv=None) -> TrainResult:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dataset-id", default="train-corpus")
    ap.add_argument("--data-root", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (default: smoke config)")
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

    model = build_model(cfg, mesh=None)
    digest = config_digest(cfg)

    def loop(resume) -> TrainResult:
        params, opt = init_train_state(model, jax.random.PRNGKey(args.seed), opt_cfg)
        sampler = SamplerState(seed=args.seed)
        start = 0
        if resume is not None and ckpt.latest_step() is not None:
            start, params, opt, sampler = ckpt.restore(template={"params": params, "opt": opt})
            print(f"[restore] resumed from step {start}")
        loader = TokenLoader(store, dspec, reader, batch=args.batch, state=sampler)
        step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0, 1))
        monitor = StragglerMonitor()
        losses = []
        it = iter(loader)

        with PreemptionGuard() as guard:
            for step in range(start, args.steps):
                t0 = time.time()
                toks, labels = next(it)
                params, opt, metrics = step_fn(
                    params, opt, {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
                )
                dt = time.time() - t0
                losses.append(metrics["loss"])
                if monitor.record(dt):
                    print(f"[straggler] step {step} took {dt:.2f}s")
                if step % 10 == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"read+dispatch {dt*1000:.0f}ms")
                if (step + 1) % args.ckpt_every == 0 or guard.should_stop:
                    ckpt.save(step + 1, params, opt, sampler=loader.state,
                              config_digest=digest)
                if guard.should_stop:
                    print("[preempt] checkpointed and exiting")
                    break
        ckpt.save(args.steps, params, opt, sampler=loader.state,
                  config_digest=digest, blocking=True)
        return TrainResult(args.steps, 0, [float(l) for l in losses], params)

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
