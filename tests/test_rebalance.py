"""Elastic membership + online re-striping: unit and property invariants.

The rebalancer's contract (``repro.core.rebalance``):

* **bounded movement** — adding 1 node to an N-member view moves at most
  ``1/N + 0.05`` of a dataset's cached bytes,
* **dual-epoch reads** — a chunk keeps serving from its old placement until
  its move commits; reads are bit-identical before/during/after,
* **real repair** — node failure triggers *timed* re-replication (peer
  copies / remote refetch), never an instant manifest fix,
* **no oversubscription** — in-flight moves reserve destination capacity, so
  admission control and placement see a mid-rebalance node as busy,
* **no chunk lost** — after any op sequence quiesces, every chunk is placed
  and the incremental counters match the manifest-scan oracle.

The op-sequence properties extend ``tests/test_invariants.py``'s oracle with
migration reservations: ``node_usage = manifest scan + in-flight dst bytes``.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    CacheManager,
    CacheState,
    DatasetSpec,
    FillTracker,
    MANIFEST_SCHEMA_VERSION,
    PlacementEngine,
    RebalanceError,
    Rebalancer,
    SimClock,
    StripeError,
    StripeManifest,
    StripeStore,
    Topology,
    TopologyConfig,
)

N_NODES = 8
ITEM_B = 100
IPC = 4


def _cluster(*, replication=1, members=(0, 1, 2, 3), capacity=1e9, migration_bw=None,
             root=None, n_items=400):
    clock = SimClock()
    topo = Topology(TopologyConfig(nodes_per_rack=N_NODES), clock)
    store = StripeStore(topo, root=root)
    cache = CacheManager(
        topo, store, clock, capacity_per_node=capacity,
        items_per_chunk=IPC, replication=replication,
    )
    cache.register(DatasetSpec("ds", "nfs://ds", n_items, ITEM_B))
    rb = Rebalancer(clock, topo, cache, members=members, migration_bw=migration_bw)
    return clock, topo, store, cache, rb


def _admit_filled(cache, topo, nodes=4, **kw):
    cache.admit("ds", topo.nodes[:nodes], **kw)
    cache.mark_filled("ds")


# --------------------------------------------------------------- manifest v3
def test_manifest_v3_roundtrip_and_legacy():
    man = StripeManifest(
        dataset_id="d", n_items=8, item_bytes=4, items_per_chunk=4,
        replication=1, node_ids=[0, 1], chunk_nodes=[[0], [1]],
        chunk_crc=[0, 0], chunk_filled=[True, True], membership_epoch=7,
    )
    back = StripeManifest.from_json(man.to_json())
    assert back.membership_epoch == 7

    # v2 blob (no membership_epoch) loads as epoch 0
    import json

    blob = json.loads(man.to_json())
    blob.pop("membership_epoch")
    blob["schema_version"] = 2
    assert StripeManifest.from_json(json.dumps(blob)).membership_epoch == 0

    # v3 blob (no chunk_dirty) loads fully clean
    blob = json.loads(man.to_json())
    blob.pop("chunk_dirty")
    blob["schema_version"] = 3
    assert not any(StripeManifest.from_json(json.dumps(blob)).chunk_dirty)

    # future versions are refused, never guessed
    blob["schema_version"] = MANIFEST_SCHEMA_VERSION + 1
    with pytest.raises(StripeError, match="newer"):
        StripeManifest.from_json(json.dumps(blob))


# ---------------------------------------------------------- movement bound
@pytest.mark.parametrize("n_members", [3, 4, 5])
def test_add_node_moves_bounded_fraction(n_members):
    """Adding 1 node to N moves <= 1/N + 0.05 of cached bytes (acceptance)."""
    members = tuple(range(n_members))
    clock, topo, store, cache, rb = _cluster(members=members)
    cache.admit("ds", topo.nodes[:n_members])
    cache.mark_filled("ds")
    man = store.manifests["ds"]
    total = sum(len(r) for r in man.chunk_nodes) * man.chunk_bytes

    done = rb.add_node(n_members)           # the next node id joins
    clock.run()
    assert done.fired
    moved = sum(p.committed_bytes for p in rb.plans)
    assert moved > 0
    assert moved / total <= 1 / n_members + 0.05
    # the view changed exactly once and is stamped into the manifest
    assert rb.epoch.value == 1
    assert man.membership_epoch == 1
    assert man.node_ids == [*members, n_members]
    # the newcomer holds its fair share and nothing was lost
    counts = {nid: 0 for nid in man.node_ids}
    for reps in man.chunk_nodes:
        assert len(reps) == man.replication
        for nid in reps:
            counts[nid] += 1
    assert counts[n_members] == len(man.chunk_nodes) // (n_members + 1)


def test_add_node_noop_when_already_member():
    clock, topo, store, cache, rb = _cluster()
    _admit_filled(cache, topo)
    ev = rb.add_node(0)
    assert ev.fired and rb.epoch.value == 0


# ------------------------------------------------------------- scale-in/fail
def test_remove_node_evacuates_all_chunks():
    clock, topo, store, cache, rb = _cluster()
    _admit_filled(cache, topo)
    done = rb.remove_node(2)
    clock.run()
    assert done.fired
    man = store.manifests["ds"]
    assert 2 not in man.node_ids
    assert all(2 not in reps for reps in man.chunk_nodes)
    assert store.bytes_on_node(2) == 0
    assert 2 not in rb.members
    assert man.membership_epoch == 1


def test_remove_last_member_refused():
    clock, topo, store, cache, rb = _cluster(members=(0,))
    with pytest.raises(RebalanceError, match="last"):
        rb.remove_node(0)


def test_fail_node_repair_is_timed_not_instant():
    """With replication=2 a failure leaves chunks under-replicated until the
    peer-copy flows land — repair takes sim time, unlike StripeStore.repair."""
    clock, topo, store, cache, rb = _cluster(replication=2, migration_bw=4000.0)
    _admit_filled(cache, topo)
    man = store.manifests["ds"]
    done = rb.fail_node(3)
    under_now = sum(1 for r in man.chunk_nodes if len(r) < 2)
    assert under_now > 0                         # loss is instant...
    assert not done.fired
    t0 = clock.now
    clock.run()
    assert done.fired and clock.now > t0         # ...repair is not
    assert all(len(r) == 2 for r in man.chunk_nodes)
    assert 3 not in man.node_ids and 3 not in rb.members


def test_fail_node_refetches_lost_chunks_from_remote():
    """replication=1: chunks wholly lost re-fetch from the remote store;
    reads fail loudly in between and recover afterwards."""
    clock, topo, store, cache, rb = _cluster(migration_bw=4000.0)
    _admit_filled(cache, topo)
    man = store.manifests["ds"]
    done = rb.fail_node(2)
    lost = [c for c, r in enumerate(man.chunk_nodes) if not r]
    assert lost
    with pytest.raises(StripeError, match="no replicas"):
        store.locate_batch("ds", np.asarray([lost[0] * IPC]), topo.nodes[0])
    clock.run()
    assert done.fired
    assert all(r for r in man.chunk_nodes)
    assert rb.metrics.counters["remote_bytes"] == len(lost) * man.chunk_bytes
    # every item resolves again
    store.locate_batch("ds", np.arange(man.n_items, dtype=np.int64), topo.nodes[0])


# --------------------------------------------------------- dual-epoch reads
def test_dual_epoch_lookup_old_until_commit():
    clock, topo, store, cache, rb = _cluster(migration_bw=400.0)
    _admit_filled(cache, topo)
    rb.add_node(4)
    # cap 400 B/s shared by 8 in-flight 400 B chunks: the first wave commits
    # at t=8, the next is mid-flight — exactly the mixed state we want
    clock.run(until=9.0)
    man = store.manifests["ds"]
    in_flight = [c for (ds, c) in store._migrating]
    assert in_flight and store.migrating_chunks("ds") == len(in_flight)
    reader = topo.nodes[0]
    locs = store.locate_batch(
        "ds", np.asarray([c * IPC for c in in_flight], dtype=np.int64), reader
    )
    assert all(nid != 4 for nid in locs)        # mid-move: old placement serves
    committed = [
        c for c, reps in enumerate(man.chunk_nodes) if 4 in reps
    ]
    assert committed                            # and committed chunks moved over
    locs = store.locate_batch(
        "ds", np.asarray([c * IPC for c in committed], dtype=np.int64), reader
    )
    assert all(nid == 4 for nid in locs)
    clock.run()


def test_reads_bit_identical_across_rebalance(tmp_path):
    """Materialized mode: every item's bytes are identical before, during and
    after an online expansion (the mid-epoch correctness acceptance)."""
    clock, topo, store, cache, rb = _cluster(
        migration_bw=2000.0, root=str(tmp_path), n_items=64,
    )
    cache.admit("ds", topo.nodes[:4], materialize=True)
    cache.mark_filled("ds")
    reader = topo.nodes[0]
    n = store.manifests["ds"].n_items
    before = [store.read_item("ds", i, reader) for i in range(n)]

    rb.add_node(4)
    seen_midflight = False
    while store._migrating or not rb.plans[0].done.fired:
        if store._migrating:
            seen_midflight = True
        for i in range(n):                      # read through the live store
            assert store.read_item("ds", i, reader) == before[i]
        nxt = clock.now + 0.05
        if clock.run(until=nxt) == clock.now and not store._migrating:
            break
        clock.run(until=nxt)
    clock.run()
    assert seen_midflight                       # the loop really read mid-move
    after = [store.read_item("ds", i, reader) for i in range(n)]
    assert after == before


def test_remove_node_during_inflight_expansion_strands_nothing():
    """remove_node while an add_node re-striping is mid-flight: transfers
    targeting the leaving node are aborted and chunks owned by the expansion
    are taken over, so the removal drains the node completely (regression:
    skipped mid-migration chunks used to strand ~20% of the dataset on a
    decommissioned node forever)."""
    clock, topo, store, cache, rb = _cluster(migration_bw=25e6, n_items=4000)
    _admit_filled(cache, topo)
    rb.add_node(4)
    clock.run(until=clock.now + 1e-4)           # expansion transfers in flight
    assert store.migrating_chunks("ds") > 0
    done = rb.remove_node(4)
    clock.run()
    assert done.fired
    man = store.manifests["ds"]
    assert 4 not in man.node_ids and 4 not in rb.members
    assert all(4 not in reps for reps in man.chunk_nodes)
    assert store.bytes_on_node(4) == 0
    assert store.migration_in_bytes(4) == 0
    assert all(len(reps) == man.replication for reps in man.chunk_nodes)


def test_fail_node_during_inflight_expansion_restores_replication():
    """Failing a node while expansion transfers are mid-flight must still
    restore the replication target everywhere (under-replicated chunks owned
    by the expansion are taken over by the repair)."""
    clock, topo, store, cache, rb = _cluster(
        replication=2, migration_bw=25e6, n_items=4000
    )
    _admit_filled(cache, topo)
    rb.add_node(4)
    clock.run(until=clock.now + 1e-4)
    assert store.migrating_chunks("ds") > 0
    done = rb.fail_node(3)
    clock.run()
    assert done.fired
    man = store.manifests["ds"]
    assert all(len(reps) == 2 and 3 not in reps for reps in man.chunk_nodes)
    assert store.bytes_on_node(3) == 0


# ------------------------------------------------- capacity + eviction guard
def test_migration_reserves_destination_capacity():
    clock, topo, store, cache, rb = _cluster(migration_bw=400.0)
    _admit_filled(cache, topo)
    rb.add_node(4)
    clock.run(until=1.0)
    assert store.migration_in_bytes(4) > 0
    man = store.manifests["ds"]
    committed = sum(1 for reps in man.chunk_nodes if 4 in reps)
    in_flight = store.migrating_chunks("ds")
    # usage charges committed AND in-flight chunks: admission cannot
    # oversubscribe the node mid-rebalance
    assert store.bytes_on_node(4) == (committed + in_flight) * man.chunk_bytes
    clock.run()
    assert store.migration_in_bytes(4) == 0


def test_eviction_blocked_while_chunks_midflight():
    clock, topo, store, cache, rb = _cluster(migration_bw=400.0)
    _admit_filled(cache, topo)
    rb.add_node(4)
    clock.run(until=1.0)
    assert store.migrating_chunks("ds") > 0
    assert cache.entries["ds"].active_readers == 1   # the rebalancer's pin
    with pytest.raises(ValueError, match="active readers"):
        cache.evict("ds")
    clock.run()
    assert cache.entries["ds"].active_readers == 0
    cache.evict("ds")                                # fine once committed


def test_ls_and_uplink_report_migration():
    clock, topo, store, cache, rb = _cluster(migration_bw=400.0)
    _admit_filled(cache, topo)
    engine = PlacementEngine(topo, cache)
    base = engine.uplink_usage(24, 0.5)
    rb.add_node(4)
    clock.run(until=1.0)
    (row,) = cache.ls()
    assert row.migrating_chunks == store.migrating_chunks("ds") > 0
    assert row.membership_epoch == 1
    # mid-rebalance the up-link budget includes the migration draw
    busy = engine.uplink_usage(24, 0.5)
    assert busy == pytest.approx(base + 400.0 / topo.cfg.tor_uplink_bw)
    clock.run()
    assert engine.uplink_usage(24, 0.5) == pytest.approx(base)


def test_placement_skips_non_members_and_busy_nodes():
    clock, topo, store, cache, rb = _cluster(members=(0, 1, 2, 3, 4))
    engine = PlacementEngine(topo, cache)
    picked = engine.choose_cache_nodes(1e6, count=8)
    assert {n.node_id for n in picked} <= rb.members


# ----------------------------------------------------- fill-plane interplay
def test_fill_lands_at_post_move_placement():
    """An unfilled chunk retargeted mid-fill lands at the NEW node: the
    prefetch plane resolves replicas at put_chunk time, not demand time."""
    clock, topo, store, cache, rb = _cluster()
    cache.admit("ds", topo.nodes[:4], on_demand=True)
    tracker = FillTracker(clock, topo, cache, "ds")
    man = store.manifests["ds"]
    chunk = 0
    (old,) = man.chunk_nodes[chunk]
    tracker.demand(chunk)                       # remote->stripe flow in flight
    store.retarget_replica("ds", chunk, old, 5)  # elastic metadata retarget
    assert store.pending_fill_bytes(5) == man.chunk_bytes
    clock.run()
    assert man.is_filled(chunk)
    assert man.chunk_nodes[chunk] == [5]
    assert store.pending_fill_bytes(5) == 0


def test_unfilled_chunks_move_as_metadata_not_flows():
    clock, topo, store, cache, rb = _cluster()
    cache.admit("ds", topo.nodes[:4], on_demand=True)   # nothing filled
    done = rb.add_node(4)
    assert done.fired                            # no bytes exist: instant
    assert clock.now == 0.0
    plan = rb.plans[0]
    assert plan.moves == [] and plan.meta_ops > 0
    man = store.manifests["ds"]
    assert sum(1 for reps in man.chunk_nodes if 4 in reps) == plan.meta_ops
    # pending-fill pressure followed the chunks to the new node
    assert store.pending_fill_bytes(4) == plan.meta_ops * man.chunk_bytes


# ------------------------------------------------------------ op properties
SIZES = {"a": 8, "b": 20, "c": 32}


def _prop_cluster(replication=1):
    clock = SimClock()
    topo = Topology(TopologyConfig(nodes_per_rack=N_NODES), clock)
    store = StripeStore(topo)
    cache = CacheManager(
        topo, store, clock, capacity_per_node=1e9,
        items_per_chunk=IPC, replication=replication,
    )
    for name, items in SIZES.items():
        cache.register(DatasetSpec(name, f"nfs://{name}", items, ITEM_B))
    rb = Rebalancer(clock, topo, cache, members=(0, 1, 2, 3), migration_bw=8000.0)
    return clock, topo, store, cache, rb


def _oracle(store):
    """Usage/pending from a manifest scan + in-flight dst reservations."""
    usage = {nid: 0 for nid in store.node_usage}
    pending = {nid: 0 for nid in store.node_usage}
    for man in store.manifests.values():
        for c, reps in enumerate(man.chunk_nodes):
            for nid in reps:
                usage[nid] += man.chunk_bytes
                if not man.is_filled(c):
                    pending[nid] += man.chunk_bytes
    for (ds, _c), (_src, dst, _kind) in store._migrating.items():
        usage[dst] += store.manifests[ds].chunk_bytes
    return usage, pending


def _apply_op(clock, topo, store, cache, rb, v):
    op = v % 8
    ds = "abc"[(v >> 3) % 3]
    node = (v >> 5) % N_NODES
    entry = cache.entries.get(ds)
    if op == 0:                                  # admit over current members
        if entry is not None and entry.state is CacheState.REGISTERED:
            members = sorted(rb.members)
            if len(members) >= 2:
                picked = [topo.node(i) for i in members[: 2 + (v >> 8) % 2]]
                cache.admit(ds, picked, on_demand=bool((v >> 7) % 2))
                if (v >> 10) % 2:
                    cache.mark_filled(ds)
                return f"admit({ds})"
        return None
    if op == 1:                                  # land one unfilled chunk
        if ds in store.manifests:
            unfilled = store.unfilled_chunks(ds)
            if len(unfilled):
                store.put_chunk(ds, int(unfilled[(v >> 7) % len(unfilled)]))
                cache.note_chunk_filled(ds)
                return f"put_chunk({ds})"
        return None
    if op == 2:                                  # scale out
        if node not in rb.members:
            rb.add_node(node)
            return f"add_node({node})"
        return None
    if op == 3:                                  # graceful scale in
        if node in rb.members and len(rb.members) > 2:
            rb.remove_node(node)
            return f"remove_node({node})"
        return None
    if op == 4:                                  # node loss + timed repair
        if node in rb.members and len(rb.members) > 2:
            rb.fail_node(node)
            return f"fail_node({node})"
        return None
    if op == 5:                                  # straggler drain (instant op)
        if ds in store.manifests:
            store.drain(ds, node)
            return f"drain({ds},{node})"
        return None
    if op == 6:                                  # let background flows land
        clock.run(until=clock.now + 0.5 * (1 + (v >> 7) % 4))
        return "run_slice"
    # op == 7: eviction attempt — blocked while the rebalancer holds a pin
    if entry is not None and entry.state in (CacheState.CACHED, CacheState.FILLING):
        try:
            cache.evict(ds)
            return f"evict({ds})"
        except ValueError:
            return f"evict({ds})->pinned"
    return None


@settings(max_examples=30, deadline=None)
@given(
    ops=st.lists(st.integers(0, 2**16), min_size=1, max_size=25),
    replication=st.sampled_from([1, 2]),
)
def test_rebalance_ops_never_drift_counters(ops, replication):
    """node_usage (incl. in-flight reservations) and pending_fill match the
    oracle after EVERY op in arbitrary elastic/maintenance interleavings."""
    clock, topo, store, cache, rb = _prop_cluster(replication)
    history = []
    for v in ops:
        trace = _apply_op(clock, topo, store, cache, rb, v)
        if trace:
            history.append(trace)
        usage, pending = _oracle(store)
        for nid in store.node_usage:
            assert store.node_usage[nid] == usage[nid], (nid, history[-6:])
            assert store.pending_fill_bytes(nid) == pending[nid], (nid, history[-6:])
            assert store.migration_in_bytes(nid) >= 0
            assert store.migration_out_bytes(nid) >= 0


@settings(max_examples=25, deadline=None)
@given(ops=st.lists(st.integers(0, 2**16), min_size=1, max_size=20))
def test_no_chunk_lost_after_quiescence(ops):
    """Whatever elastic ops ran: once the sim drains, every surviving
    dataset's chunks are placed on live members, replication is restored,
    and scalar/vector lookup agree."""
    clock, topo, store, cache, rb = _prop_cluster(replication=2)
    history = []
    for v in ops:
        trace = _apply_op(clock, topo, store, cache, rb, v)
        if trace:
            history.append(trace)
    clock.run()                                  # quiesce all repair flows
    assert not store._migrating
    for ds, man in store.manifests.items():
        for c, reps in enumerate(man.chunk_nodes):
            assert reps, (ds, c, history[-8:])   # no chunk lost
            assert len(set(reps)) == len(reps)   # no duplicate placement
            assert len(reps) == man.replication, (ds, c, reps, history[-8:])
        reader = topo.node(sorted(rb.members)[0])
        items = np.arange(0, man.n_items, IPC, dtype=np.int64)
        batch = store.locate_batch(ds, items, reader)
        for k in (0, len(items) - 1):
            assert batch[k] == store.locate(ds, int(items[k]), reader).node_id
