"""Stripe store: round-trips, replication, corruption repair, node loss."""

import base64
import dataclasses
import json
import os
import zlib

import numpy as np
import pytest

from repro.core import (
    MANIFEST_SCHEMA_VERSION,
    SimClock,
    StripeError,
    StripeManifest,
    StripeStore,
    Topology,
    TopologyConfig,
)
from repro.core import hostspans, stripestore
from repro.core.stripestore import ChunkCorruption


@pytest.fixture()
def topo():
    return Topology(TopologyConfig(nodes_per_rack=4, racks_per_pod=2), SimClock())


def _mk_store(topo, tmp_path):
    return StripeStore(topo, root=str(tmp_path))


def test_round_trip_real_bytes(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    payloads = {c: bytes([c % 256]) * 1024 for c in range(10)}
    store.create("ds", n_items=40, item_bytes=256, nodes=topo.nodes[:4],
                 items_per_chunk=4, materialize=True, payload=lambda c: payloads[c])
    for item in (0, 5, 17, 39):
        raw = store.read_item("ds", item, topo.nodes[0])
        chunk = item // 4
        off = (item % 4) * 256
        assert raw == payloads[chunk][off : off + 256]


def test_striping_balances_nodes(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    store.create("ds", n_items=64, item_bytes=128, nodes=topo.nodes[:4],
                 items_per_chunk=4, materialize=True)
    usage = [store.bytes_on_node(n.node_id) for n in topo.nodes[:4]]
    assert max(usage) == min(usage) > 0


def test_locate_prefers_local_replica(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    store.create("ds", n_items=16, item_bytes=64, nodes=topo.nodes[:4],
                 items_per_chunk=4, replication=2, materialize=True)
    for item in range(16):
        src = store.locate("ds", item, topo.nodes[0])
        replicas = store.manifests["ds"].chunk_nodes[item // 4]
        if 0 in replicas:
            assert src.node_id == 0


def test_corruption_repaired_from_replica(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=8, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=2, replication=2, materialize=True)
    victim = man.chunk_nodes[0][0]
    path = store._chunk_path("ds", victim, 0)
    with open(path, "wb") as fh:
        fh.write(b"garbage")
    blob = store.read_chunk_verified("ds", 0, topo.nodes[victim])
    assert len(blob) == man.chunk_bytes


def test_corrupt_replica_rewritten_on_fallback(topo, tmp_path):
    """Satellite regression: falling back to a healthy copy used to leave
    the corrupt replica in place, so every subsequent nearby reader re-read
    and re-CRCed the bad copy.  The fallback must heal it in place."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=8, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=2, replication=2, materialize=True)
    victim = man.chunk_nodes[0][0]
    path = store._chunk_path("ds", victim, 0)
    with open(path, "wb") as fh:
        fh.write(b"garbage")
    blob = store.read_chunk_verified("ds", 0, topo.nodes[victim])
    assert len(blob) == man.chunk_bytes
    assert store.corruption_repairs == 1
    # the corrupt copy was rewritten from the healthy one: a direct read of
    # the victim replica now CRC-verifies
    assert store._read_chunk(man, victim, 0) == blob
    # and a second verified read needs no further repair
    store.read_chunk_verified("ds", 0, topo.nodes[victim])
    assert store.corruption_repairs == 1


def test_read_item_falls_back_and_heals_corrupt_replica(topo, tmp_path):
    """The product read path (HoardFS.pread ends here) must survive a
    corrupt chosen replica: fall through to a healthy copy and heal the bad
    one in place instead of hard-failing the read."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=8, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=2, replication=2, materialize=True)
    reader = topo.nodes[0]
    victim = store.locate("ds", 0, reader).node_id   # what this read resolves to
    with open(store._chunk_path("ds", victim, 0), "wb") as fh:
        fh.write(b"garbage")
    raw = store.read_item("ds", 0, reader)           # must not raise
    assert len(raw) == 64
    assert store.corruption_repairs == 1
    assert len(store._read_chunk(man, victim, 0)) == man.chunk_bytes  # healed


def test_read_item_heals_corrupt_replica_sorting_after_healthy_one(topo, tmp_path):
    """Regression: a corrupt replica that sorts AFTER the first healthy one
    in distance order must still heal when read_item passes it as the
    known-bad skip_replica — the heal loop only rewrites replicas collected
    before the healthy read, so it has to be seeded up front."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=64, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=2, replication=2, materialize=True)
    reader = topo.nodes[4]                    # other rack: every pick is a tie
    for item in range(64):                    # find a hash that picks slot 1 —
        chunk = item // 2                     # the replica a stable distance
        reps = man.chunk_nodes[chunk]         # sort visits last
        if store.locate("ds", item, reader).node_id == reps[1]:
            break
    else:
        pytest.fail("tie-break never picked slot 1 across 32 chunks")
    victim = reps[1]
    with open(store._chunk_path("ds", victim, chunk), "wb") as fh:
        fh.write(b"bad")
    raw = store.read_item("ds", item, reader)
    assert len(raw) == 64
    assert store.corruption_repairs == 1
    assert len(store._read_chunk(man, victim, chunk)) == man.chunk_bytes  # healed


def test_missing_replica_restored_on_fallback(topo, tmp_path):
    """A replica whose file vanished is re-placed from the healthy copy."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=8, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=2, replication=2, materialize=True)
    victim = man.chunk_nodes[0][0]
    os.remove(store._chunk_path("ds", victim, 0))
    blob = store.read_chunk_verified("ds", 0, topo.nodes[victim])
    assert store.corruption_repairs == 1
    assert store._read_chunk(man, victim, 0) == blob


def test_all_replicas_corrupt_raises(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=4, item_bytes=64, nodes=topo.nodes[:2],
                       items_per_chunk=2, replication=2, materialize=True)
    for nid in man.chunk_nodes[0]:
        with open(store._chunk_path("ds", nid, 0), "wb") as fh:
            fh.write(b"bad")
    with pytest.raises(ChunkCorruption):
        store.read_chunk_verified("ds", 0, topo.nodes[0])


def test_node_failure_and_repair(topo, tmp_path):
    """Beyond-paper: losing a cache node re-replicates without remote refetch."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=32, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=4, replication=2, materialize=True)
    store.fail_node(2)
    under = [c for c, reps in enumerate(man.chunk_nodes) if len(reps) < 2]
    assert under, "node 2 held replicas"
    created = store.repair("ds")
    assert created == len(under)
    assert all(len(reps) == 2 for reps in man.chunk_nodes)
    # every item still readable with verified contents
    for item in range(32):
        assert len(store.read_item("ds", item, topo.nodes[0])) == 64


def test_delete_frees_space(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    store.create("ds", n_items=16, item_bytes=64, nodes=topo.nodes[:4],
                 items_per_chunk=4, materialize=True)
    assert sum(store.node_usage.values()) > 0
    store.delete("ds")
    assert sum(store.node_usage.values()) == 0
    assert not os.path.exists(os.path.join(str(tmp_path), "node0", "ds"))


def test_locate_batch_vectorised_matches_scalar(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    store.create("ds", n_items=100, item_bytes=32, nodes=topo.nodes[:3],
                 items_per_chunk=7, materialize=False)
    items = np.arange(100)
    batch = store.locate_batch("ds", items, topo.nodes[1])
    for i in items:
        assert batch[i] == store.locate("ds", int(i), topo.nodes[1]).node_id


def test_locate_batch_agrees_with_locate_after_maintenance(topo, tmp_path):
    """Regression: the replication==1 fast path derived nodes from the
    ORIGINAL round-robin layout (node_ids[chunk % nn]); after drain/
    fail_node/repair rewrite chunk_nodes it returned stale nodes."""
    store = _mk_store(topo, tmp_path)
    store.create("ds", n_items=96, item_bytes=32, nodes=topo.nodes[:4],
                 items_per_chunk=4, replication=1, materialize=False)
    moved = store.drain("ds", node_id=1)          # rewrite chunk placements
    assert moved > 0
    items = np.arange(96)
    batch = store.locate_batch("ds", items, topo.nodes[0])
    for i in items:
        assert batch[i] == store.locate("ds", int(i), topo.nodes[0]).node_id
    assert not np.any(batch == 1)                  # drained node serves nothing

    # unrepaired data loss (replication 1, node gone): healthy-chunk batches
    # still serve; batches touching a lost chunk fail loudly like locate()
    store.fail_node(0)
    man = store.manifests["ds"]
    healthy = [c for c, reps in enumerate(man.chunk_nodes) if reps]
    dead = [c for c, reps in enumerate(man.chunk_nodes) if not reps]
    assert dead, "node 0 held sole replicas"
    ok_items = np.asarray([c * 4 for c in healthy])
    batch = store.locate_batch("ds", ok_items, topo.nodes[3])
    for k, i in enumerate(ok_items):
        assert batch[k] == store.locate("ds", int(i), topo.nodes[3]).node_id
    from repro.core import StripeError
    with pytest.raises(StripeError, match="no replicas"):
        store.locate_batch("ds", np.asarray([dead[0] * 4]), topo.nodes[3])

    # same property after a node failure + repair cycle (replication 2)
    store.create("ds2", n_items=64, item_bytes=32, nodes=topo.nodes[:4],
                 items_per_chunk=4, replication=2, materialize=False)
    store.fail_node(2)
    store.repair("ds2")
    items = np.arange(64)
    batch = store.locate_batch("ds2", items, topo.nodes[3])
    for i in items:
        assert batch[i] == store.locate("ds2", int(i), topo.nodes[3]).node_id


# ------------------------------------------------------------ manifest schema
def test_manifest_schema_round_trip(topo, tmp_path):
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=40, item_bytes=256, nodes=topo.nodes[:4],
                       items_per_chunk=4, replication=2, materialize=True)
    blob = man.to_json()
    assert json.loads(blob)["schema_version"] == MANIFEST_SCHEMA_VERSION
    again = StripeManifest.from_json(blob)
    assert dataclasses.asdict(again) == dataclasses.asdict(man)


def test_manifest_legacy_blob_back_compat():
    """Pre-versioning blobs (no schema_version, empty/missing chunk_filled)
    must load and read as fully filled — HoardFS metadata can evolve without
    stranding old on-disk manifests."""
    legacy = {
        "dataset_id": "old", "n_items": 16, "item_bytes": 64,
        "items_per_chunk": 4, "replication": 1, "node_ids": [0, 1],
        "chunk_nodes": [[0], [1], [0], [1]], "chunk_crc": [0, 0, 0, 0],
        "materialized": False,
    }                                        # note: no chunk_filled at all
    man = StripeManifest.from_json(json.dumps(legacy))
    assert man.chunk_filled == []
    assert man.n_filled == man.n_chunks == 4
    assert all(man.is_filled(c) for c in range(4))
    # empty-mask spelling round-trips unchanged through the current writer
    again = StripeManifest.from_json(man.to_json())
    assert again.chunk_filled == [] and again.n_filled == 4


def test_manifest_future_schema_refused():
    with pytest.raises(StripeError, match="newer"):
        StripeManifest.from_json(json.dumps({"schema_version": MANIFEST_SCHEMA_VERSION + 1}))


# ------------------------------------------- maintenance vs partially-filled
def _partial_fill_setup(topo, tmp_path):
    """Materialized on-demand dataset with chunks 0..3 filled, 4..7 pending."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=32, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=4, materialize=True, prefill=False)
    for c in range(4):
        store.put_chunk("ds", c)
    return store, man


def _total_pending(store, topo):
    return sum(store.pending_fill_bytes(n.node_id) for n in topo.nodes)


def test_drain_preserves_fill_mask_on_partial_dataset(topo, tmp_path):
    """drain() must move a filled chunk's real bytes but only retarget the
    metadata of an unfilled one — and the chunk_filled mask itself must
    survive the replica moves untouched."""
    store, man = _partial_fill_setup(topo, tmp_path)
    mask_before = list(man.chunk_filled)
    pending_before = _total_pending(store, topo)
    moved = store.drain("ds", node_id=1)
    assert moved > 0
    assert man.chunk_filled == mask_before              # mask survives the move
    assert store.bytes_on_node(1) == 0
    assert store.pending_fill_bytes(1) == 0
    assert _total_pending(store, topo) == pending_before  # conserved, just moved
    # filled chunks stay readable from their new homes (real bytes + CRC)
    for item in range(16):
        assert len(store.read_item("ds", item, topo.nodes[0])) == 64
    # unfilled chunks were retargeted without inventing files on disk
    for c in store.unfilled_chunks("ds"):
        for nid in man.chunk_nodes[c]:
            assert not os.path.exists(store._chunk_path("ds", nid, int(c)))
    # the fill completes against the post-drain layout
    for c in store.unfilled_chunks("ds"):
        store.put_chunk("ds", int(c))
    assert store.filled_fraction("ds") == 1.0
    assert _total_pending(store, topo) == 0
    for item in range(32):
        assert len(store.read_item("ds", item, topo.nodes[0])) == 64


def test_repair_after_node_loss_on_partial_dataset(topo, tmp_path):
    """fail_node + repair mid-fill: filled chunks re-replicate with bytes,
    unfilled chunks re-replicate as metadata only, and the eventual
    put_chunk writes every (new) replica."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=32, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=4, replication=2, materialize=True,
                       prefill=False)
    for c in range(4):
        store.put_chunk("ds", c)
    mask_before = list(man.chunk_filled)
    store.fail_node(2)
    under = [c for c, reps in enumerate(man.chunk_nodes) if len(reps) < 2]
    assert under
    created = store.repair("ds")
    assert created == len(under)
    assert man.chunk_filled == mask_before              # mask survives repair
    assert all(len(reps) == 2 for reps in man.chunk_nodes)
    # metadata-only repair: no files for unfilled chunks anywhere
    for c in store.unfilled_chunks("ds"):
        for nid in man.chunk_nodes[c]:
            assert not os.path.exists(store._chunk_path("ds", nid, int(c)))
    for c in store.unfilled_chunks("ds"):
        store.put_chunk("ds", int(c))
    # every replica of every chunk now holds verifiable bytes
    for c, reps in enumerate(man.chunk_nodes):
        for nid in reps:
            assert len(store._read_chunk(man, nid, c)) == man.chunk_bytes
    assert _total_pending(store, topo) == 0


def test_drain_straggler_node(topo, tmp_path):
    """Straggler mitigation: drain() migrates a slow node's chunks to the
    least-loaded peers and every item stays readable (real bytes, CRC)."""
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=32, item_bytes=64, nodes=topo.nodes[:4],
                       items_per_chunk=4, materialize=True)
    moved = store.drain("ds", node_id=1)
    assert moved > 0
    assert store.bytes_on_node(1) == 0
    assert all(1 not in reps for reps in man.chunk_nodes)
    for item in range(32):
        assert len(store.read_item("ds", item, topo.nodes[0])) == 64


# ------------------------------------------------- item-range reads (schema v5)
BLOCK, ITEM, IPC = 256, 100, 10          # 1000-byte chunks: blocks 256, 256, 256, 232


@pytest.fixture()
def small_blocks(monkeypatch):
    """New manifests record 256-byte CRC blocks, so a chunk spans four."""
    monkeypatch.setattr(stripestore, "CRC_BLOCK_BYTES", BLOCK)


def _payload(c: int) -> bytes:
    return np.random.default_rng(c).bytes(ITEM * IPC)


def _blocked_store(topo, tmp_path, *, replication=2, **kw):
    store = _mk_store(topo, tmp_path)
    man = store.create("ds", n_items=4 * IPC, item_bytes=ITEM, nodes=topo.nodes[:4],
                       items_per_chunk=IPC, replication=replication, materialize=True,
                       payload=_payload, **kw)
    return store, man


def _item(c: int, slot: int) -> bytes:
    return _payload(c)[slot * ITEM : (slot + 1) * ITEM]


def _flip(store, node_id, chunk, at):
    path = store._chunk_path("ds", node_id, chunk)
    with open(path, "r+b") as fh:
        fh.seek(at)
        b = fh.read(1)
        fh.seek(at)
        fh.write(bytes([b[0] ^ 0xFF]))


def _read_counted(store, item, reader):
    with hostspans.batch():
        raw = store.read_item("ds", item, reader)
    return raw, hostspans.last(1)[0].counters


def _tables_match_disk(store, man):
    """Every replica file's whole and block CRCs are the manifest's."""
    for c, reps in enumerate(man.chunk_nodes):
        if not reps or not man.is_filled(c):
            continue
        for nid in reps:
            with open(store._chunk_path(man.dataset_id, nid, c), "rb") as fh:
                blob = fh.read()
            assert zlib.crc32(blob) == man.chunk_crc[c]
            want = [zlib.crc32(blob[k : k + man.crc_block_bytes])
                    for k in range(0, len(blob), man.crc_block_bytes)]
            assert man.block_crcs(c).tolist() == want


@pytest.mark.parametrize("slot", [0, 2, 5, 8, 9],
                         ids=["chunk-start", "across-256", "across-512", "short-last-block",
                              "chunk-end"])
def test_range_read_round_trips(small_blocks, topo, tmp_path, slot):
    store, man = _blocked_store(topo, tmp_path)
    assert man.crc_block_bytes == BLOCK and len(man.block_crcs(0)) == 4
    for c in range(man.n_chunks):
        raw, counters = _read_counted(store, c * IPC + slot, topo.nodes[0])
        assert raw == _item(c, slot)
        assert counters["stripe.range_reads"] == 1 and "stripe.fallbacks" not in counters
    assert store.corruption_repairs == 0


def test_range_read_at_the_default_block_size(topo, tmp_path):
    """24,000-byte items in 192,000-byte chunks: three 64 KiB blocks, the last
    short; item 2 straddles the first boundary and reads two blocks."""
    store = _mk_store(topo, tmp_path)
    payload = np.random.default_rng(7).bytes(8 * 24_000)
    man = store.create("big", n_items=8, item_bytes=24_000, nodes=topo.nodes[:1],
                       items_per_chunk=8, materialize=True, payload=lambda c: payload)
    assert man.crc_block_bytes == stripestore.CRC_BLOCK_BYTES == 64 * 1024
    assert len(man.block_crcs(0)) == 3
    for item in range(8):
        with hostspans.batch():
            raw = store.read_item("big", item, topo.nodes[0])
        (rec,) = hostspans.last(1)
        assert raw == payload[item * 24_000 : (item + 1) * 24_000]
        assert rec.counters["stripe.range_reads"] == 1
    assert rec.counters["stripe.bytes_read"] == 192_000 - 2 * 65_536     # the short block


def test_flipped_byte_in_the_items_block_falls_back_and_heals(small_blocks, topo, tmp_path):
    store, man = _blocked_store(topo, tmp_path)
    reader = topo.nodes[0]
    victim = store.locate("ds", 15, reader).node_id          # chunk 1, bytes 500-600
    _flip(store, victim, 1, 520)
    raw, counters = _read_counted(store, 15, reader)
    assert raw == _item(1, 5)
    assert counters["stripe.fallbacks"] == 1 and "stripe.range_reads" not in counters
    assert store.corruption_repairs == 1
    assert store._read_chunk(man, victim, 1) == _payload(1)  # healed in place


def test_flipped_byte_in_another_block_is_left_to_the_whole_chunk_check(
        small_blocks, topo, tmp_path):
    """An item read checks what it reads: a flip in block 3 does not touch an
    item of block 0, and the whole-chunk verified read still finds and heals it."""
    store, man = _blocked_store(topo, tmp_path)
    reader = topo.nodes[0]
    victim = store.locate("ds", 10, reader).node_id
    _flip(store, victim, 1, 900)
    raw, counters = _read_counted(store, 10, reader)
    assert raw == _item(1, 0)
    assert counters["stripe.range_reads"] == 1 and "stripe.fallbacks" not in counters
    assert store.corruption_repairs == 0
    with pytest.raises(ChunkCorruption):
        store._read_chunk(man, victim, 1)
    assert store.read_chunk_verified("ds", 1, topo.nodes[victim]) == _payload(1)
    assert store.corruption_repairs == 1
    assert store._read_chunk(man, victim, 1) == _payload(1)


@pytest.mark.parametrize("slot", [5, 9], ids=["block-cut-short", "block-gone"])
def test_truncated_replica_falls_back_and_heals(small_blocks, topo, tmp_path, slot):
    store, man = _blocked_store(topo, tmp_path)
    reader = topo.nodes[0]
    victim = store.locate("ds", IPC + slot, reader).node_id
    os.truncate(store._chunk_path("ds", victim, 1), 600)     # mid block 2
    raw, counters = _read_counted(store, IPC + slot, reader)
    assert raw == _item(1, slot)
    assert counters["stripe.fallbacks"] == 1
    assert store.corruption_repairs == 1
    assert store._read_chunk(man, victim, 1) == _payload(1)


def test_bad_block_without_a_second_replica_raises(small_blocks, topo, tmp_path):
    store, man = _blocked_store(topo, tmp_path, replication=1)
    _flip(store, man.chunk_nodes[2][0], 2, 300)
    with pytest.raises(ChunkCorruption):
        store.read_item("ds", 2 * IPC + 3, topo.nodes[0])
    assert store.read_item("ds", 2 * IPC + 0, topo.nodes[0]) == _item(2, 0)


def test_v4_manifest_loads_and_reads_whole_chunks(small_blocks, topo, tmp_path):
    store, man = _blocked_store(topo, tmp_path)
    blob = json.loads(man.to_json())
    for key in ("crc_block_bytes", "chunk_block_crcs"):
        blob.pop(key)
    blob["schema_version"] = 4
    old = StripeManifest.from_json(json.dumps(blob))
    assert old.chunk_block_crcs == [] and old.block_crcs(0) is None
    store.manifests["ds"] = old
    raw, counters = _read_counted(store, 12, topo.nodes[0])
    assert raw == _item(1, 2)
    assert counters == {"stripe.bytes_read": ITEM * IPC, "stripe.bytes_delivered": ITEM}


def test_v5_manifest_round_trips_its_block_table(small_blocks, topo, tmp_path):
    store, man = _blocked_store(topo, tmp_path)
    d = json.loads(man.to_json())
    assert d["schema_version"] == 5 and d["crc_block_bytes"] == BLOCK
    table = np.frombuffer(base64.b64decode(d["chunk_block_crcs"][3]), dtype="<u4")
    want = [zlib.crc32(_payload(3)[k : k + BLOCK]) for k in range(0, ITEM * IPC, BLOCK)]
    assert table.tolist() == want
    again = StripeManifest.from_json(man.to_json())
    assert dataclasses.asdict(again) == dataclasses.asdict(man)
    _tables_match_disk(store, again)


def test_range_reads_verify_after_every_write_of_new_bytes(small_blocks, topo, tmp_path):
    """put_chunk, commit_writes and a refetch's commit_transfer each write new
    bytes with fresh tables: reads that follow find no false corruption."""
    store, man = _blocked_store(topo, tmp_path, replication=1, prefill=False)
    for c in range(man.n_chunks):
        store.put_chunk("ds", c, payload=_payload)
    _tables_match_disk(store, man)
    reader = topo.nodes[0]
    writer = man.chunk_nodes[1][0]
    store.write_pending("ds", 1, 250, b"w" * 300, writer)   # blocks 0-2
    store.commit_writes("ds", [1], writer)
    _tables_match_disk(store, man)
    lost = man.chunk_nodes[2][0]
    store.fail_node(lost)                                    # chunk 2 has no replica left
    dst = next(n.node_id for n in topo.nodes[:4] if n.node_id != lost)
    assert store.begin_transfer("ds", 2, None, dst, kind="refetch")
    assert store.commit_transfer("ds", 2)
    _tables_match_disk(store, man)
    written = bytearray(_payload(1))
    written[250:550] = b"w" * 300
    refetched = store.remote_payload(man, 2)
    assert refetched != _payload(2)
    for slot in range(IPC):
        for c, want in ((0, _payload(0)), (1, bytes(written)), (2, refetched)):
            raw, counters = _read_counted(store, c * IPC + slot, reader)
            assert raw == want[slot * ITEM : (slot + 1) * ITEM]
            assert counters["stripe.range_reads"] == 1, (c, slot)
    assert store.corruption_repairs == 0
