"""Serving driver: batched generation over a Hoard-cached prompt set.

Demonstrates the cache's cross-job reuse for inference: prompt datasets stay
striped in the cache between engine restarts (dataset lifecycle decoupled
from the serving job), so a rolling deploy never re-reads the remote store.

    python -m repro.launch.serve --arch qwen1.5-0.5b --requests 8 --new-tokens 16
"""

from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from ..configs import ARCHS
from ..core import build_cluster
from ..data import TokenDatasetSpec, materialize_token_dataset
from ..models import build_model, params as PM
from ..serve import ServeConfig, ServingEngine
from .compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch].smoke()
    model = build_model(cfg, mesh=None)
    params = PM.materialize(model.layout(), jax.random.PRNGKey(args.seed), cfg.dtype)

    # prompts live in the Hoard cache (striped, CRC-verified)
    clock, topo, store, cache, engine = build_cluster()
    store.root = tempfile.mkdtemp(prefix="hoard_serve_")
    dspec = TokenDatasetSpec("prompts", n_sequences=max(64, args.requests),
                             seq_len=args.prompt_len, vocab=cfg.vocab, seed=args.seed)
    materialize_token_dataset(store, cache, dspec, topo.nodes[:4], items_per_chunk=8)
    prompts = np.stack([
        np.frombuffer(store.read_item("prompts", i, topo.nodes[0]), np.int32)
        for i in range(args.requests)
    ])

    cache_len = args.prompt_len + args.new_tokens + 8
    srv = ServingEngine(model, params, cache_len=cache_len, batch=args.requests)
    t0 = time.time()
    out = srv.generate(prompts, ServeConfig(max_new_tokens=args.new_tokens,
                                            temperature=args.temperature, seed=args.seed))
    dt = time.time() - t0
    tps = args.requests * args.new_tokens / dt
    print(f"generated {out.shape} tokens in {dt:.2f}s ({tps:.1f} tok/s)")
    for i in range(min(2, args.requests)):
        print(f"req{i}: {out[i][:12].tolist()}")


if __name__ == "__main__":
    main()
