"""The real-clock recorder of the read path (``repro.core.hostspans``).

Covers the ring bound, parent and self time, batch-id windowing, the exact
``stripe.*`` counters on a materialized store (a corrupt replica and a
remote read-through included), the spans' place on the profiler's host plane
and clock against the batch totals, the benchmark's readers of them, the
trainer's tenth-step line, and the named scopes the train step's ops carry.
"""

import importlib.util
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.core import (
    CacheManager,
    DatasetSpec,
    HostSpans,
    SimClock,
    StripeStore,
    Topology,
    TopologyConfig,
    build_cluster,
    hostspans,
)
from repro.data import TokenDatasetSpec, TokenLoader, materialize_token_dataset
from repro.models import build_model
from repro.train import AdamWConfig, init_train_state, make_train_step

METRICS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "metrics"
STRIPE_SPANS = ("stripe.locate", "stripe.io", "stripe.verify")
SEQ, IPC = 16, 4                  # 64-byte token items, 4 to a chunk
ITEM_B, CHUNK_B = SEQ * 4, SEQ * 4 * IPC


def _sleep_span(rec, name, s=0.002):
    with rec.span(name):
        time.sleep(s)


# ------------------------------------------------------------------ recorder
def test_ring_keeps_the_newest_batches():
    rec = HostSpans(batches=3)
    for _ in range(5):
        with rec.batch():
            with rec.span("stripe.io"):
                pass
    assert [b.batch for b in rec.batches] == [2, 3, 4]
    assert all(set(b.total_ns) == {hostspans.BATCH_SPAN, "stripe.io"} for b in rec.batches)
    assert [b.batch for b in rec.last(3)] == [2, 3, 4] and rec.last(4) is None


def test_parent_and_self_time():
    rec = HostSpans()
    with rec.batch():
        _sleep_span(rec, "stripe.io")
        _sleep_span(rec, "stripe.verify")
        _sleep_span(rec, "stripe.verify")
        time.sleep(0.002)
    (b,) = rec.last(1)
    tot = b.total_ns
    assert tot["stripe.verify"] >= 2 * 0.002e9 and tot["stripe.io"] >= 0.002e9
    # the parent's total holds its children's; what is left is its own time
    own = tot[hostspans.BATCH_SPAN] - tot["stripe.io"] - tot["stripe.verify"]
    assert own >= 0.002e9


def test_windowing_by_batch_id():
    rec = HostSpans()
    assert rec.last(1) is None
    for i in range(4):
        with rec.batch():
            rec.count("stripe.bytes_read", i)
    assert rec.last(5) is None and rec.last(0) is None
    assert [b.counters["stripe.bytes_read"] for b in rec.last(2)] == [2, 3]
    assert [b.batch for b in rec.last(4)] == [0, 1, 2, 3]


def test_work_outside_a_batch_is_charged_to_none():
    rec = HostSpans()
    with rec.span("stripe.io"):
        rec.count("stripe.bytes_read")
    assert rec.last(1) is None
    with rec.batch():
        pass
    (b,) = rec.last(1)
    assert set(b.total_ns) == {hostspans.BATCH_SPAN} and not b.counters


def test_a_batch_that_raises_is_not_held():
    rec = HostSpans()
    with pytest.raises(OSError):
        with rec.batch():
            raise OSError("disk")
    assert rec.last(1) is None
    with rec.batch():
        with pytest.raises(RuntimeError, match="already open"):
            with rec.batch():
                pass


# ------------------------------------------------------------------ counters
@pytest.fixture()
def corpus(tmp_path):
    _, topo, store, cache, _ = build_cluster()
    store.root = str(tmp_path)
    spec = TokenDatasetSpec("hs", n_sequences=64, seq_len=SEQ, vocab=100)
    materialize_token_dataset(store, cache, spec, topo.nodes[:4], items_per_chunk=IPC)
    return store, spec, topo.nodes[0]


def _read_batches(store, spec, reader, n, batch=4):
    it = iter(TokenLoader(store, spec, reader, batch=batch))
    for _ in range(n):
        next(it)
    return hostspans.last(n)


def test_counters_are_exact_on_a_materialized_store(corpus):
    recs = _read_batches(*corpus, n=3)
    for r in recs:
        c = r.counters
        # every item read reads its whole chunk: four chunk reads a batch
        assert c["stripe.bytes_read"] == 4 * CHUNK_B and "stripe.fallbacks" not in c
        assert c["stripe.bytes_delivered"] == 4 * ITEM_B
        assert set(STRIPE_SPANS) <= set(r.total_ns)
    assert hostspans.read_amplification(recs) == IPC


def _store(tmp_path, ds, *, replication=1, **admit):
    """A materialized 24-item store of 100-byte items, 4 to a chunk."""
    clock = SimClock()
    topo = Topology(TopologyConfig(nodes_per_rack=4), clock)
    store = StripeStore(topo, root=str(tmp_path))
    cache = CacheManager(topo, store, clock, capacity_per_node=1e9, items_per_chunk=IPC,
                         replication=replication)
    cache.register(DatasetSpec(ds, f"nfs://{ds}", 24, 100))
    cache.admit(ds, topo.nodes[:2], materialize=True, **admit)
    cache.mark_filled(ds)
    return store, topo.nodes[0]


def test_corrupt_replica_is_one_fallback_and_its_bytes_are_read(tmp_path):
    store, reader = _store(tmp_path, "cr", replication=2)
    want = store.read_item("cr", 5, reader)
    bad = store.locate("cr", 5, reader).node_id
    path = Path(store._chunk_path("cr", bad, 1))
    blob = bytearray(path.read_bytes())
    blob[0] ^= 0xFF
    path.write_bytes(bytes(blob))
    with hostspans.batch():
        got = store.read_item("cr", 5, reader)
    (r,) = hostspans.last(1)
    assert got == want
    assert r.counters == {"stripe.bytes_read": 2 * IPC * 100, "stripe.fallbacks": 1,
                          "stripe.bytes_delivered": 100}


@pytest.fixture()
def small_blocks(monkeypatch):
    """New manifests record 256-byte CRC blocks: a 400-byte chunk spans two."""
    from repro.core import stripestore

    monkeypatch.setattr(stripestore, "CRC_BLOCK_BYTES", 256)


@pytest.mark.parametrize("slot, read", [(0, 256), (2, 400), (3, 144)],
                         ids=["first-block", "both-blocks", "short-last-block"])
def test_range_read_counters_are_exact_on_a_multi_block_store(small_blocks, tmp_path,
                                                              slot, read):
    store, reader = _store(tmp_path, "rr")
    with hostspans.batch():
        store.read_item("rr", 4 + slot, reader)
    (r,) = hostspans.last(1)
    assert r.counters == {"stripe.bytes_read": read, "stripe.range_reads": 1,
                          "stripe.bytes_delivered": 100}


def test_loader_range_reads_are_counted_per_item(small_blocks, tmp_path):
    """64-byte token items, 16 to a 1,024-byte chunk: each item read reads its
    one 256-byte block, so the amplification is 4 where whole chunks gave 16."""
    _, topo, store, cache, _ = build_cluster()
    store.root = str(tmp_path)
    spec = TokenDatasetSpec("rb", n_sequences=64, seq_len=SEQ, vocab=100)
    materialize_token_dataset(store, cache, spec, topo.nodes[:4], items_per_chunk=16)
    recs = _read_batches(store, spec, topo.nodes[0], n=3)
    for r in recs:
        assert r.counters == {"stripe.bytes_read": 4 * 256, "stripe.range_reads": 4,
                              "stripe.bytes_delivered": 4 * ITEM_B}
    assert hostspans.read_amplification(recs) == 4


def test_corrupt_block_falls_back_to_a_whole_chunk_read(small_blocks, tmp_path):
    store, reader = _store(tmp_path, "cb", replication=2)
    bad = store.locate("cb", 6, reader).node_id
    path = Path(store._chunk_path("cb", bad, 1))
    blob = bytearray(path.read_bytes())
    blob[300] ^= 0xFF
    path.write_bytes(bytes(blob))
    with hostspans.batch():
        store.read_item("cb", 6, reader)                 # bytes 200-300: both blocks
        store.read_item("cb", 4, reader)                 # bytes 0-100: the healed copy
    (r,) = hostspans.last(1)
    # the failed range read (400), the healthy replica's whole chunk (400),
    # then one block of the healed replica (256)
    assert r.counters == {"stripe.bytes_read": 400 + 400 + 256, "stripe.fallbacks": 1,
                          "stripe.range_reads": 1, "stripe.bytes_delivered": 200}


def test_remote_read_through_counts_delivery_and_opens_no_io(tmp_path):
    store, reader = _store(tmp_path, "rt", fraction=0.5)
    assert not store.manifests["rt"].chunk_nodes[5]
    with hostspans.batch():
        store.read_item("rt", 20, reader)
    (r,) = hostspans.last(1)
    assert r.counters == {"stripe.bytes_delivered": 100}
    assert set(r.total_ns) == {hostspans.BATCH_SPAN}


# ------------------------------------------------------------------ profiler
def test_spans_sit_on_the_host_plane_on_the_profilers_clock(corpus, tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation

    store, spec, reader = corpus
    it = iter(TokenLoader(store, spec, reader, batch=4))
    next(it)                                   # the first read pays the imports
    jax.profiler.start_trace(str(tmp_path / "trace"))
    t_before = time.time_ns()
    with TraceAnnotation("data.read"):
        next(it)
        next(it)
    t_after = time.time_ns()
    jax.profiler.stop_trace()
    recs = hostspans.last(2)

    (pb,) = (tmp_path / "trace").rglob("*.xplane.pb")
    data = ProfileData.from_file(str(pb))
    start = None
    events = {}
    for plane in data.planes:
        start = dict(plane.stats).get("profile_start_time", start)
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/host"):
                    events.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    assert start is not None
    (outer,) = events["data.read"]
    slack = 50_000
    # the profiler stamps host events on time.time_ns's clock
    assert t_before - slack <= start + outer[0] <= start + outer[1] <= t_after + slack
    batches = sorted(events[hostspans.BATCH_SPAN])
    assert len(batches) == 2
    for (b0, b1), r in zip(batches, recs):
        assert outer[0] <= b0 <= b1 <= outer[1]
        assert abs(r.total_ns[hostspans.BATCH_SPAN] - (b1 - b0)) <= slack
        for name in STRIPE_SPANS:
            inside = [(e0, e1) for e0, e1 in events[name] if b0 <= e0 <= e1 <= b1]
            assert len(inside) == 4            # four item reads, each of a whole chunk
            assert abs(r.total_ns[name] - sum(e1 - e0 for e0, e1 in inside)) <= 4 * slack
    assert sum(len(events[n]) for n in STRIPE_SPANS) == 2 * 3 * 4


# ------------------------------------------------------------------ readers
def _metric(name):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_metric_readers_read_the_window_batches(corpus):
    recs = _read_batches(*corpus, n=5)
    rec = {"steps": 3}                         # the window is the last three
    window = recs[-3:]
    for name, span in (("data.verify_ms", "stripe.verify"), ("data.io_ms", "stripe.io"),
                       ("data.locate_ms", "stripe.locate")):
        want = sum(r.total_ns[span] for r in window) / 3 / 1e6
        assert _metric(name)(rec) == pytest.approx(want, rel=1e-12) and want > 0
    assert _metric("data.read_amplification")(rec) == IPC
    held = len(hostspans.RECORDER.batches)
    for name in ("data.verify_ms", "data.io_ms", "data.locate_ms", "data.read_amplification"):
        assert _metric(name)({"steps": held + 1}) is None


# ------------------------------------------------------------------ trainer
def test_trainer_prints_read_path_and_step_time(tmp_path, monkeypatch, capsys):
    from repro.launch import train

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    train.main(["--steps", "11", "--batch", "2", "--seq", "32", "--ckpt-every", "20",
                "--ckpt-dir", str(tmp_path / "ckpt"), "--data-root", str(tmp_path / "data")])
    lines = [s for s in capsys.readouterr().out.splitlines() if s.startswith("step ")]
    assert len(lines) == 2 and "read" not in lines[0]
    assert " step " in lines[1] and "read locate/io/verify" in lines[1]
    # stripe_token_corpus puts 16 items in a chunk, and every item read is a chunk read
    assert lines[1].endswith("amplification 16x fallbacks 0")


# ------------------------------------------------------------------ scopes
def test_train_step_ops_carry_the_named_scopes():
    cfg = ARCHS["qwen1.5-0.5b"].smoke()
    model = build_model(cfg, mesh=None)
    opt_cfg = AdamWConfig()
    params, opt = jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0), opt_cfg))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(make_train_step(model, opt_cfg)).lower(
        params, opt, {"tokens": tok, "labels": tok}).as_text(debug_info=True)
    # a scope is one element of an op's name stack, inside a transform's
    # parentheses where it was differentiated: jvp(logits_loss)/reduce_max
    names = re.findall(r'loc\("([^"]*)"', text)
    for scope in ("embed", "attention", "mlp", "logits_loss", "optimizer"):
        assert any(re.search(rf"(^|[/(]){scope}[/)]", n) for n in names), scope
