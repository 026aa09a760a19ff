"""One run of one cell: set-up, the measured window, the check, the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything the
harness needs about it is found by name:

* ``configs/<config>.json`` — the model as it runs (Hugging Face keys), the
  program's architecture name, the mesh, and the reference family;
* ``traffic/<traffic>.json`` — corpus, chunking, batch, order, optimizer;
* ``limits/<workload>.json`` — the limit of each number that decides ``correct``;
* ``metrics/<metric>.py`` — one reader per per-layer metric;
* ``families/<family>.py`` — the program's config and FLOPs of a family;
* ``reference/<family>.py`` — the plain float32 reference.

The window drives the trainer's main path as ``repro.launch.train.main``
composes it, call for call: ``TokenLoader`` over ``StripeStore.read_item``,
``jnp.asarray`` of the batch (``device_put`` to the batch sharding on a
mesh), the jitted train step with donated state, and a host read of the
loss and gradient norm every ``loss_fetch_every`` steps.  ``train.main`` has
no loop entry to call, so ``Loop.step`` mirrors its loop body.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import queue
import shutil
import statistics
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
SPANS = ("data.read", "h2d", "dispatch", "loss_fetch")
WINDOW_SPAN = "window"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ the cell
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, repo: Path = REPO) -> Cell:
    bench = json.loads((repo / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((repo / cfg["file"]).read_text()),
        traffic=json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((HERE / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: Cell):
    return _module(HERE / "reference" / f"{cell.config['program']['family']}.py")


def model_config(cell: Cell):
    """The program's ``ModelConfig``, with every size from the configuration file."""
    from flops import family
    return family(cell.config).model_config(cell.config)


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans: summed on the host clock, and written into the profiler's
    trace as ``TraceAnnotation``s when a trace is being taken."""

    def __init__(self):
        self.totals = {n: 0.0 for n in SPANS}
        self.annotate = False

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.owner.totals[self.name] += time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)


# ------------------------------------------------------------------ program
class Program:
    """The system under test, built once per process for one cell."""

    def __init__(self, cell: Cell, devices, fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from repro.configs import ShapeConfig
        from repro.launch.mesh import make_test_mesh
        from repro.launch.sharded_step import build_sharded_step
        from repro.models import build_model
        from repro.train import AdamWConfig, init_opt_state, make_train_step

        c, t = cell.config, cell.traffic
        self.cell, self.fault = cell, fault
        self.ref = reference_module(cell)
        self.ref_steps: dict = {}
        self.spec = self.ref.param_spec(c)
        self.opt_cfg = AdamWConfig(**t["optimizer"])
        self.cfg = model_config(cell)
        mesh_shape = c["program"]["mesh"]
        B, S = t["batch"], t["seq_len"]
        self.mesh = None
        if mesh_shape:
            self.mesh = make_test_mesh(data=mesh_shape["data"], model=mesh_shape["model"],
                                       devices=devices)
            st = build_sharded_step(self.cfg, ShapeConfig(cell.name, S, B, "train"), self.mesh,
                                    self.opt_cfg)
            self.layout, step_fn = st.layout, st.jitted
            self.param_sharding, self.opt_sharding = st.param_sharding, st.opt_sharding
            batch_sharding = st.batch_sharding
            self.put = lambda toks, labels: jax.device_put(
                {"tokens": toks, "labels": labels}, batch_sharding)
        else:
            model = build_model(self.cfg, mesh=None)
            self.layout = model.layout()
            step_fn = jax.jit(make_train_step(model, self.opt_cfg), donate_argnums=(0, 1))
            self.param_sharding = self.opt_sharding = jax.sharding.SingleDeviceSharding(
                devices[0])
            self.put = lambda toks, labels: {"tokens": jnp.asarray(toks),
                                             "labels": jnp.asarray(labels)}
        self._check_layout()
        self.step_fn = _planted(fault, step_fn, self)
        opt_cfg = self.opt_cfg
        self.init_opt = jax.jit(lambda p: init_opt_state(p, opt_cfg),
                                out_shardings=self.opt_sharding)
        b1 = opt_cfg.b1
        norms = lambda tree: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)
        from weights import init_fn
        init = init_fn(self.spec, self.cfg.dtype)
        # the first gradient as the optimizer got it: mu = (1 - b1) * clipped g
        self.grad_norms = jax.jit(lambda opt: norms(
            jax.tree.map(lambda m: m / (1 - b1), opt["mu"])))
        # the parameters' change, from the fp32 master copy the next step reads
        self.change_norms = jax.jit(lambda opt, seed: norms(
            jax.tree.map(lambda m, p0: m - p0.astype(jnp.float32), opt["master"], init(seed))))

    def _check_layout(self):
        import jax
        from repro.models import params as PM
        from weights import shapes
        got = jax.tree.map(lambda i: tuple(i.shape), self.layout,
                           is_leaf=lambda x: isinstance(x, PM.ParamInfo))
        want = shapes(self.spec)
        if got != want:
            raise ValueError(f"program's parameter layout differs from the reference's:\n"
                             f"{got}\n{want}")


def _planted(fault: Optional[str], step_fn, prog: "Program"):
    """The step, or the step with one fault planted underneath: for the tests
    and for `calibrate.py`'s fault readings, never for a benchmark run."""
    if fault is None or fault == "token_altered":
        return step_fn
    if fault == "frozen_state":
        import jax

        from repro.models import build_model
        from repro.train import make_train_step
        plain = jax.jit(make_train_step(build_model(prog.cfg, mesh=None), prog.opt_cfg))
        return lambda p, o, b: (p, o, plain(p, o, b)[2])
    if fault == "no_exchange":
        # each chip steps on its own shard of the batch, and no gradient
        # crosses between chips; the state read back is the first chip's
        if prog.mesh is None:
            raise ValueError("no_exchange needs a mesh")
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.models import build_model
        from repro.train import make_train_step
        local = jax.shard_map(
            make_train_step(build_model(prog.cfg, mesh=None), prog.opt_cfg), mesh=prog.mesh,
            in_specs=(P(), P(), P("data")), out_specs=(P(), P(), P()), check_vma=False)
        return jax.jit(local, in_shardings=(prog.param_sharding, prog.opt_sharding, None),
                       out_shardings=(prog.param_sharding, prog.opt_sharding, None),
                       donate_argnums=(0, 1))
    if fault == "half_batch":
        part = prog.cell.traffic["batch"] // 2
        put = prog.put
        prog.put = lambda toks, labels: put(toks[:part], labels[:part])
        return step_fn
    raise ValueError(f"unknown fault {fault!r}")


class Run:
    """One seed's state: the stripe store and loader, params and optimizer."""

    def __init__(self, prog: Program, seed: int, workdir: Path):
        import jax

        from repro.core import build_cluster
        from repro.data import TokenDatasetSpec, TokenLoader, materialize_token_dataset
        from repro.train import SamplerState
        from weights import make

        t, c = prog.cell.traffic, prog.cell.config
        self.prog, self.seed = prog, seed
        self.spans = Spans()
        _, topo, store, cache, _ = build_cluster()
        store.root = str(workdir)
        self.dspec = TokenDatasetSpec(
            f"bench-{prog.cell.name}", n_sequences=t["items_per_chunk"] * t["n_chunks"],
            seq_len=t["seq_len"], vocab=c["vocab_size"], seed=seed)
        materialize_token_dataset(store, cache, self.dspec, topo.nodes[:t["nodes"]],
                                  items_per_chunk=t["items_per_chunk"],
                                  replication=t["replication"])
        if prog.fault == "token_altered":
            _alter_first_item(store, self.dspec.dataset_id)
        self.loader = TokenLoader(store, self.dspec, topo.nodes[0], batch=t["batch"],
                                  state=SamplerState(seed=seed))
        self.it = iter(self.loader)
        self.params = make(prog.spec, seed, prog.cfg.dtype, prog.param_sharding)
        self.opt = prog.init_opt(self.params)
        jax.block_until_ready((self.params, self.opt))
        self.n = 0                    # steps taken since the seed
        self.delivered: list[tuple[int, object, object]] = []
        self.every = t["loss_fetch_every"]

    def step(self):
        """One iteration of the trainer's loop body; returns the step's metrics."""
        p, sp = self.prog, self.spans
        with sp("data.read"):
            toks, labels = next(self.it)
        self.delivered.append((self.n, toks, labels))
        with sp("h2d"):
            batch = p.put(toks, labels)
        with sp("dispatch"):
            self.params, self.opt, metrics = p.step_fn(self.params, self.opt, batch)
        if self.n % self.every == 0:
            with sp("loss_fetch"):
                float(metrics["loss"])
                float(metrics["grad_norm"])
        self.n += 1
        return metrics

    def free(self):
        self.params = self.opt = self.it = self.loader = None
        gc.collect()


def _alter_first_item(store, dataset_id: str):
    """Fault: the first item the store hands back has its first token changed."""
    read = store.read_item
    calls = []

    def altered(ds, item, reader):
        raw = read(ds, item, reader)
        calls.append(item)
        if len(calls) == 1:
            return bytes([raw[0] ^ 1]) + raw[1:]
        return raw

    store.read_item = altered


# ------------------------------------------------------------------ the check
def worst_leaf_gap(got: dict, want: dict, leaves: Optional[set] = None) -> tuple[float, str]:
    """Largest |got - want| over leaves, each against max(want leaf, median want)."""
    g = dict(_flat(got))
    w = dict(_flat(want))
    if set(g) != set(w):
        raise ValueError(f"leaf sets differ: {sorted(set(g) ^ set(w))}")
    med = statistics.median(w.values())
    worst, where = 0.0, ""
    for k in sorted(w):
        if leaves is not None and k not in leaves:
            continue
        gap = abs(g[k] - w[k]) / max(w[k], med)
        if not math.isfinite(gap):
            gap = math.inf
        if gap > worst or where == "":
            worst, where = gap, k
    return worst, where


def _flat(tree) -> list[tuple[str, float]]:
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append(("/".join(str(getattr(p, "key", p)) for p in path), float(leaf)))
    return out


def moved_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the median
    leaf's; the others (a key's bias under softmax) move by round-off alone."""
    flat = dict(_flat(ref_grad_norms))
    med = statistics.median(flat.values())
    return {k for k, v in flat.items() if v >= 1e-3 * med}


def warm_readings(run: Run) -> dict:
    """Drive the first ``reference_steps`` steps through the window's own call
    and feed, reading what the reference is compared with."""
    import jax
    prog, k = run.prog, run.prog.cell.traffic["reference_steps"]
    losses, grads = [], None
    for i in range(k):
        m = run.step()
        losses.append(float(m["loss"]))
        if i == 0:
            grads = jax.device_get(prog.grad_norms(run.opt))
    import jax.numpy as jnp
    change = jax.device_get(prog.change_norms(run.opt, jnp.uint32(run.seed % 2**32)))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def reference_readings(prog: Program, seed: int, low: bool = False) -> dict:
    """The reference's first steps on the corpus regenerated from the seed."""
    from reference.corpus import Corpus
    from weights import make
    c, t = prog.cell.config, prog.cell.traffic
    corpus = Corpus(t, c["vocab_size"], seed)
    batches = [corpus.batch_at(i) for i in range(t["reference_steps"])]
    step = prog.ref_steps.setdefault(low, prog.ref.make_step(c, t["optimizer"], low))
    return prog.ref.train(c, t["optimizer"], lambda: make(prog.spec, seed, c["torch_dtype"]),
                          batches, low=low, step=step)


def compare(prog_r: dict, ref_r: dict) -> dict:
    """The numbers that decide ``correct`` (besides the data check)."""
    loss_gap = max(abs(a - b) for a, b in zip(prog_r["losses"], ref_r["losses"]))
    if not all(math.isfinite(x) for x in prog_r["losses"]):
        loss_gap = math.inf
    grad_gap, grad_leaf = worst_leaf_gap(prog_r["grad_norms"], ref_r["grad_norms"])
    moved = moved_leaves(ref_r["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(prog_r["change_norms"], ref_r["change_norms"],
                                             moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "grad_leaf": grad_leaf,
            "change_gap": change_gap, "change_leaf": change_leaf,
            "leaves_left_out": sorted(set(dict(_flat(ref_r["grad_norms"]))) - moved)}


def rows_wrong(run: Run) -> int:
    from reference.corpus import Corpus
    c, t = run.prog.cell.config, run.prog.cell.traffic
    corpus = Corpus(t, c["vocab_size"], run.seed)
    return sum(corpus.rows_wrong(n, toks, labels) for n, toks, labels in run.delivered)


# ------------------------------------------------------------------ the run
def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_cache():
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    # cache every program, however quickly it compiles, so that set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *, t_process: float,
             fault: Optional[str] = None,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)) -> dict:
    """Set up, measure ``seconds``, check; return the result line's object."""
    import jax

    from compilelog import CompileLog
    compiles = CompileLog()
    cache_dir = enable_cache()
    devices = chips(cell.chips)
    dev0 = devices[0]
    workdir = Path(tempfile.mkdtemp(prefix="hoard-bench-"))
    trace_dir = workdir / "trace"
    try:
        prog = Program(cell, devices, fault)
        run = Run(prog, seed, workdir / "stripes")
        warm = warm_readings(run)
        if trace:
            jax.profiler.start_trace(str(trace_dir), profiler_options=profile_options())
            run.spans.annotate = True
        before = compiles.compiles
        setup_s = time.perf_counter() - t_process
        log(f"[setup] {setup_s:.3f}s {compiles.report()} cache={cache_dir}")

        # ---- the measured window ---------------------------------------
        done: queue.Queue = queue.Queue()
        completions: list[float] = []
        losses: list[float] = []

        def watch():
            while True:
                item = done.get()
                if item is None:
                    return
                item.block_until_ready()
                completions.append(time.perf_counter())
                losses.append(float(item))

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        for s in run.spans.totals:
            run.spans.totals[s] = 0.0
        first = run.n
        ann = jax.profiler.TraceAnnotation(WINDOW_SPAN) if trace else None
        if ann:
            ann.__enter__()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            done.put(run.step()["loss"])
        done.put(None)
        watcher.join()
        t1 = completions[-1]
        if ann:
            ann.__exit__(None, None, None)
        steps = run.n - first
        in_window = compiles.compiles - before
        peak = _peak_bytes(devices)
        if trace:
            jax.profiler.stop_trace()
            run.spans.annotate = False
        spans = dict(run.spans.totals)
        t = cell.traffic
        tokens = steps * t["batch"] * t["seq_len"]
        log(f"[window] {steps} steps in {t1 - t0:.3f}s; compiles in window {in_window}")

        # ---- the check, once the window has closed ----------------------
        wrong = rows_wrong(run)
        delivered = sum(len(x[1]) for x in run.delivered)
        run.free()
        ref = reference_readings(prog, seed)
        numbers = compare(warm, ref)
        nonfinite = sum(not math.isfinite(x) for x in losses)
        checks = {
            "rows_wrong": (wrong, cell.limits["rows_wrong"]),
            "loss_gap": (numbers["loss_gap"], cell.limits["loss_gap"]),
            "grad_gap": (numbers["grad_gap"], cell.limits["grad_gap"]),
            "change_gap": (numbers["change_gap"], cell.limits["change_gap"]),
            "nonfinite_losses": (nonfinite, 0),
            "window_compiles": (in_window, 0),
        }
        correct = all(v <= lim for v, lim in checks.values())
        log(f"[check] rows delivered {delivered}; worst grad leaf {numbers['grad_leaf']}; "
            f"worst change leaf {numbers['change_leaf']}; left out of the change "
            f"{numbers['leaves_left_out']}")
        log(f"[check] program losses {warm['losses']} reference {ref['losses']}")

        result = {"correct": correct, "attempted": steps, "failed": nonfinite}
        device = {"platform": dev0.platform, "kind": dev0.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        rec = {"steps": steps, "spans": spans, "chips": len(devices),
               "flops_per_step": _flops_per_step(cell), "device_kind": dev0.device_kind}
        if trace:
            red = _reduce_trace(trace_dir, devices)
            rec["trace"] = red
            device.update(busy_s=red.mean_busy_s, window_s=red.window_s)
            metrics = _per_layer(cell, rec)
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                                   "idle_gaps": [list(x) for x in red.idle_by_span]}
        else:
            result["metrics"] = _end_to_end(cell, tokens, t0, t1, setup_s)
            result["device"] = device
        result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
        for k, (v, lim) in checks.items():
            log(f"{k} {v} limit {lim}")
        return result
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _flops_per_step(cell: Cell) -> float:
    from flops import train_flops_per_token
    t = cell.traffic
    return train_flops_per_token(cell.config, t["seq_len"]) * t["batch"] * t["seq_len"]


def _end_to_end(cell: Cell, tokens: int, t0: float, t1: float, setup_s: float) -> dict:
    from window import rate
    values = {"tokens_per_s": rate(tokens, t0, t1), "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}


def _reduce_trace(trace_dir: Path, devices):
    import trace_reduce as tr
    t = tr.load(str(trace_dir), set(SPANS) | {WINDOW_SPAN})
    win = [s for s in t.spans if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"trace holds {len(win)} window spans, want 1")
    _, lo, hi = win[0]
    t.spans = [s for s in t.spans if s[0] != WINDOW_SPAN]
    ids = sorted(t.ops)[:len(devices)]
    return tr.reduce(t, lo, hi, ids)


def _per_layer(cell: Cell, rec: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = _module(HERE / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
