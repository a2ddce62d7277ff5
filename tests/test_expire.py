"""The cluster at capacity under a retention policy: configuration
az1-ec12p4-expire (benchmark/configs), the benchmark cell az1.put16m-expire.

Every byte ingested is matched by a byte expired, and DELETE, the two-phase
deleter, punch-out and chunk compaction run under the writers. Held here, on
the CPU at small sizes, against the plain store model
(benchmark/reference_expire.py): a deleted object is not-found through the
gateway and a live one byte-equal; every live shard equals the reference row
before, while and after its chunk is compacted; a PUT and a GET of a chunk
complete while its compaction's copy is held; records appended and deleted
during the copy are right after the swap; a mark-delete is durable before
anything is punched; the deleter drains a backlog without a tick; the tick
waits for neither; the counters and the backlog gauge move by the model's
numbers; a crash before a compaction's commit leaves the old generation
served."""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from chubaofs_tpu import chaos
from chubaofs_tpu.blobstore import blobnode as bn
from chubaofs_tpu.blobstore.access import BlobDeleted
from chubaofs_tpu.blobstore.blobnode import BlobNode, NoSuchShard, ShardDeleted
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.gateway import AccessGateway
from chubaofs_tpu.blobstore.proxy import TOPIC_BLOB_DELETE, TOPIC_SHARD_REPAIR
from chubaofs_tpu.blobstore.taskswitch import SWITCH_BLOB_DELETE
from chubaofs_tpu.utils.exporter import registry

from test_azdown import _json, _load

reference = _load("reference")
reference_expire = _load("reference_expire")
CONFIG = _json("configs", "az1-ec12p4-expire.json")
TRAFFIC = _json("traffic", "put16m-expire.json")
CELL = "az1.put16m-expire"
LAYOUT = dict(n_nodes=CONFIG["layout"]["nodes"], disks_per_node=CONFIG["layout"]["disks_per_node"])
SIZES = (100_000, 600_000, 3_000_000, 5_000_000)  # EC3P3, EC6P3, EC12P4 one blob, EC12P4 two


def counter(name, labels=None, role="blobnode"):
    return registry(role).counter(name, labels).value


def gauge(name, role="scheduler"):
    return registry(role).gauge(name).value


def stage_count(stage):
    return registry("trace").summary("stage_seconds", {"stage": stage}).snapshot()["count"]


def record_len(payload):
    return reference_expire.record_bytes(payload, CONFIG["record_framing"])


@pytest.fixture
def cluster(tmp_path):
    c = MiniCluster(str(tmp_path), **LAYOUT)
    yield c
    c.close()


def http_get(addr, token):
    host, _, port = addr.rpartition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("POST", "/get", json.dumps({"location": token, "offset": 0, "size": -1}))
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def http_delete(addr, token):
    host, _, port = addr.rpartition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request("POST", "/delete", token)
        resp = conn.getresponse()
        resp.read()
        return resp.status
    finally:
        conn.close()


def stored_shards(c, loc):
    """[[bytes | None a stripe position] a blob], as benchmark/deploy.py reads them."""
    out = []
    for b in loc.blobs:
        row = []
        for u in c.cm.get_volume(b.vid).units:
            try:
                row.append(c.nodes[u.node_id].get_shard(u.vuid, b.bid))
            except Exception:
                row.append(None)
        out.append(row)
    return out


def assert_shards_are_the_reference(c, model, token, loc):
    for ref, got in zip(model.stripes(token), stored_shards(c, loc)):
        assert [s == r.tobytes() for s, r in zip(got, ref)] == [True] * len(ref)


def live_bytes(c):
    """Bytes of live records, and the account beside them: a datafile's length
    never falls with a delete; what the filesystem holds of it does, to the
    live records where it takes the punch."""
    chunks = [ch for n in c.nodes.values() for d in n.disks.values() for ch in d.chunks.values()]
    stats = [d for n in c.nodes.values() for d in n.stats()["disks"]]
    live = sum(ch.live for ch in chunks)
    assert sum(d["used"] for d in stats) == sum(ch.used for ch in chunks) == live + sum(ch.holes for ch in chunks)
    held = sum(d["held"] for d in stats)
    assert held == (live if counter("punch_failed") == 0 else held) and live <= held <= live + sum(ch.holes for ch in chunks)
    return live


# -- the configuration, its traffic file and the benchmark's index say one thing ----


def test_configuration_is_az1_at_capacity_under_retention():
    base = _json("configs", "az1-ec12p4.json")
    for key in ("layout", "policies", "modes", "max_blob_size", "cache_plane", "code"):
        assert CONFIG[key] == base[key], key
    assert "task_switches_off" not in CONFIG and "switches_off" not in TRAFFIC and "nodes_down" not in TRAFFIC
    bench = _json("..", "BENCHMARK.json")
    entry = bench["configs"][-1]
    assert entry["name"] == CONFIG["name"] and entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, CONFIG["name"], "put16m-expire", 1)
    assert all(1 <= len(e["why"]) <= 200 for g in ("configs", "workloads") for e in bench[g])
    put = next(m for m in bench["end_to_end"] if m["name"] == "put_MBps")
    assert put["workloads"] == ["az1.put16m", "az3.put16m", "az2.put16m", CELL]
    p, put16m = TRAFFIC["params"], _json("traffic", "put16m.json")
    for key in ("streams", "object_bytes", "stagger_s"):
        assert p[key] == put16m["params"][key], key
    assert TRAFFIC["verify"]["sample_objects"] == put16m["verify"]["sample_objects"]
    assert p["apply_within_s"] == CONFIG["retention"]["apply_within_s"] == 15 and p["probes_per_s"] == 2
    assert TRAFFIC["load_disk_bytes"] == p["objects"] * p["object_bytes"] * 1.4
    named = [e["name"] for e in bench["per_layer"] if e.get("workloads") == [CELL]]
    assert len(named) == 27 and all(n.startswith("expire_") for n in named)
    # the rule the configuration states is the program's
    rule = CONFIG["retention"]["compaction"]
    assert rule["min_hole_ratio"] == BlobNode.COMPACT_MIN_HOLE_RATIO
    assert rule["chunk_max_bytes"] == bn.Disk.DEFAULT_CHUNK_SIZE == 2 * rule["min_chunk_bytes"]
    assert (CONFIG["record_framing"]["header_bytes"], CONFIG["record_framing"]["crc_block_bytes"]) == (
        bn.HEADER_LEN, bn.crc32block.BLOCK_SIZE)


def test_the_store_model_against_a_hand_worked_case():
    # a 16 MiB object: 4 blobs of 4 MiB, EC12P4: 16 shards of ceil(4 MiB / 12) = 349,526 bytes,
    # 6 CRC blocks each -> 32 + 349,526 + 24 a record
    assert reference_expire.blob_sizes(16 << 20, 4 << 20) == [4 << 20] * 4
    assert reference_expire.record_bytes(349_526, CONFIG["record_framing"]) == 349_582
    assert reference_expire.stored_bytes(16 << 20, CONFIG) == 4 * 16 * 349_582 == 22_373_248
    # 1 byte: EC3P3, 6 shards of the 2 KiB minimum
    assert reference_expire.stored_bytes(1, CONFIG) == 6 * (32 + 2048 + 4)
    m = reference_expire.Store(CONFIG)
    m.put("a", b"x" * 5_000_000)
    m.put("b", b"y")
    assert m.get("a") == b"x" * 5_000_000 and m.get("nope") is None
    assert [s.shape for s in m.stripes("a")] == [(16, 349_526), (16, 67_142)]
    assert m.live_stored_bytes() == reference_expire.stored_bytes(5_000_000, CONFIG) + 6 * 2084
    m.delete("a")
    m.delete("never put")
    assert m.get("a") is None and m.live_stored_bytes() == 6 * 2084 and m.deleted == {"a", "never put"}


# -- the system against the model ------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2147483999])
def test_the_system_answers_as_the_model_does(cluster, seed):
    c, rng = cluster, np.random.default_rng(seed)
    gw = AccessGateway(c.access)
    model = reference_expire.Store(CONFIG)
    try:
        locs = {}
        for n, size in enumerate(SIZES * 2):
            data = rng.bytes(size)
            loc = c.access.put(data)
            locs[loc.to_json()] = loc
            model.put(loc.to_json(), data)
        assert live_bytes(c) == model.live_stored_bytes()
        for token in list(locs)[::2]:
            assert http_delete(gw.addr, token) == 200
            model.delete(token)
        stats = c.run_background_once()
        assert stats["deletes"] == sum(len(locs[t].blobs) for t in model.deleted)
        assert c.proxy.delete_backlog() == 0
        for token, loc in locs.items():
            status, body = http_get(gw.addr, token)
            want = model.get(token)
            assert (status, body if want is not None else None) == ((200, want) if want is not None else (404, None))
            if want is not None:
                assert_shards_are_the_reference(c, model, token, loc)
            else:
                assert all(s is None for row in stored_shards(c, loc) for s in row)
        assert live_bytes(c) == model.live_stored_bytes()
    finally:
        gw.stop()


def test_a_get_of_a_deleted_object_is_not_found_at_once_and_makes_no_work(cluster, rng):
    c = cluster
    loc = c.access.put(rng.bytes(3_000_000))
    c.access.delete(loc)
    c.run_background_once()
    repair = c.proxy.topics[TOPIC_SHARD_REPAIR]
    before = (repair.lag("scheduler"), counter("read_plan_total", {"plan": "two_round"}, "access"),
              counter("read_bytes", {"kind": "decoded"}, "access"))
    with pytest.raises(BlobDeleted):
        c.access.get(loc)
    assert (repair.lag("scheduler"), counter("read_plan_total", {"plan": "two_round"}, "access"),
            counter("read_bytes", {"kind": "decoded"}, "access")) == before
    unit = c.cm.get_volume(loc.blobs[0].vid).units[0]
    with pytest.raises(ShardDeleted):
        c.nodes[unit.node_id].get_shard(unit.vuid, loc.blobs[0].bid)
    with pytest.raises(NoSuchShard) as never:
        c.nodes[unit.node_id].get_shard(unit.vuid, 1 << 40)
    assert not isinstance(never.value, ShardDeleted)


def test_a_mark_on_one_unit_already_answers_not_found(cluster, rng):
    """Phase one runs on every unit before the first punch: from the first
    mark on, a GET is not-found (never bytes of a blob being deleted)."""
    c = cluster
    loc = c.access.put(rng.bytes(3_000_000))
    blob = loc.blobs[0]
    unit = c.cm.get_volume(blob.vid).units[5]
    assert c.nodes[unit.node_id].mark_delete_shards(unit.vuid, [blob.bid]) == 1
    with pytest.raises(BlobDeleted):
        c.access.get(loc)


# -- two phases, a chunk's batch a lock take -------------------------------------------


def one_chunk(tmp_path, rng, n=8, size=4096, vuid=7):
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    node.create_vuid(vuid)
    shards = {bid: rng.bytes(size) for bid in range(n)}
    for bid, data in shards.items():
        node.put_shard(vuid, bid, data)
    return node, node._chunk(vuid), shards


def test_a_batch_is_one_lock_take_and_one_index_write_a_phase(tmp_path, rng):
    node, chunk, shards = one_chunk(tmp_path, rng)
    writes = []
    sound = chunk._db.write_batch
    chunk._db.write_batch = lambda puts=(), deletes=(): (writes.append(len(list(puts))), sound(puts=puts, deletes=deletes))[1]
    stages = stage_count("chunk.delete")
    holes, deleted = counter("hole_bytes"), counter("shard_delete")
    punched = counter("punched_bytes") + counter("punch_failed") * record_len(4096)
    assert node.mark_delete_shards(7, [0, 1, 2, 99]) == 3  # 99: not held, passed by
    assert writes == [3]
    for bid in (0, 1, 2):
        with pytest.raises(ShardDeleted):
            node.get_shard(7, bid)
    assert chunk.holes == 0  # nothing released by the mark
    assert node.delete_shards(7, [0, 1, 2, 99]) == 3
    assert writes == [3, 3] and stage_count("chunk.delete") == stages + 2
    assert chunk.holes == 3 * record_len(4096) and chunk.tombstones == {0, 1, 2}
    assert counter("hole_bytes") == holes + 3 * record_len(4096)
    # taken by the filesystem or refused (a 9p root has no PUNCH_HOLE), each punch is counted once
    assert counter("punched_bytes") + counter("punch_failed") * record_len(4096) == punched + 3 * record_len(4096)
    assert counter("shard_delete") == deleted + 3
    assert node.get_shard(7, 3) == shards[3]
    assert node.delete_shards(7, [0, 1, 2]) == 0  # idempotent
    node.close()


def test_a_mark_delete_is_durable_before_anything_is_punched(tmp_path, rng):
    """Killed between the two: the reopened chunk does not serve the shard,
    and the replayed delete punches it."""
    node, chunk, shards = one_chunk(tmp_path, rng)
    chaos.arm("blobnode.delete_punch", "error(killed)", times=1)
    with pytest.raises(chaos.FailpointError):
        node.delete_shards(7, [4])  # unmarked on arrival: marked first, then the kill
    assert chunk.holes == 0
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    assert node._chunk(7).shards[4].status == bn.STATUS_MARK_DELETE
    with pytest.raises(ShardDeleted):
        node.get_shard(7, 4)
    assert node.get_shard(7, 5) == shards[5]
    assert node.delete_shards(7, [4]) == 1  # the replay
    assert node.has_tombstone(7, 4) and node._chunk(7).holes == record_len(4096)
    node.close()


# -- compaction: copy outside the lock, catch up and swap under it ---------------------


def held_compaction(chunk):
    """Start chunk.compact() on a thread and hold it after its copy."""
    chaos.arm("blobnode.compact_copy", "hang", times=1)
    out = {}
    t = threading.Thread(target=lambda: out.update(reclaimed=chunk.compact()))
    t.start()
    deadline = time.monotonic() + 10
    while chaos.fired("blobnode.compact_copy") < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert chaos.fired("blobnode.compact_copy") == 1
    return t, out


@pytest.mark.parametrize("when", ["before", "while_the_copy_is_held", "after"])
def test_every_live_shard_is_the_reference_row_around_a_compaction(cluster, rng, when):
    c = cluster
    model = reference_expire.Store(CONFIG)
    locs = {}
    for size in (3_000_000, 3_000_000, 5_000_000, 3_000_000):
        data = rng.bytes(size)
        loc = c.access.put(data)
        locs[loc.to_json()] = loc
        model.put(loc.to_json(), data)
    for token in list(locs)[:2]:
        c.access.delete(locs[token])
        model.delete(token)
    c.run_background_once()  # the rule picks no chunk this small: holes stay
    chunks = [c.nodes[u.node_id]._chunk(u.vuid) for loc in locs.values() for b in loc.blobs
              for u in c.cm.get_volume(b.vid).units]
    holey = next(ch for ch in chunks if ch.holes)
    t = None
    if when == "while_the_copy_is_held":
        t, _ = held_compaction(holey)
    elif when == "after":
        assert sum(ch.compact() for ch in {id(ch): ch for ch in chunks if ch.holes}.values()) > 0
        assert all(ch.holes == 0 for ch in chunks)
    try:
        for token, loc in locs.items():
            if model.get(token) is not None:
                assert c.access.get(loc) == model.get(token)
                assert_shards_are_the_reference(c, model, token, loc)
    finally:
        chaos.release("blobnode.compact_copy")
        if t is not None:
            t.join(10)
    assert live_bytes(c) == model.live_stored_bytes()


def test_a_put_and_a_get_complete_while_the_copy_is_held(tmp_path, rng):
    """Guarantee 3: a PUT or GET of a chunk never waits for a compaction's
    copy, only for its swap."""
    node, chunk, shards = one_chunk(tmp_path, rng)
    node.delete_shards(7, [0, 1])
    swaps = stage_count("chunk.compact_swap")
    t, out = held_compaction(chunk)
    try:
        t0 = time.monotonic()
        assert node.get_shard(7, 3) == shards[3]
        new = rng.bytes(4096)
        node.put_shard(7, 100, new)
        assert node.get_shard(7, 100) == new
        assert time.monotonic() - t0 < 2.0 and t.is_alive() and chunk.gen == 0
        assert stage_count("chunk.compact_swap") == swaps  # nothing held the lock yet
    finally:
        chaos.release("blobnode.compact_copy")
        t.join(10)
    assert not t.is_alive() and chunk.gen == 1 and stage_count("chunk.compact_swap") == swaps + 1
    assert out["reclaimed"] == 2 * record_len(4096)  # of generation 0 with what was appended meanwhile
    assert node.get_shard(7, 100) == new and node.get_shard(7, 3) == shards[3]
    node.close()


def test_what_was_appended_deleted_and_re_put_during_the_copy_is_right_after_the_swap(tmp_path, rng):
    node, chunk, shards = one_chunk(tmp_path, rng)
    node.delete_shards(7, [0])
    copied0, reclaimed0 = counter("compact_bytes", {"kind": "copied"}), counter("compact_bytes", {"kind": "reclaimed"})
    t, _ = held_compaction(chunk)
    appended = {bid: rng.bytes(3000 + bid) for bid in (20, 21)}
    for bid, data in appended.items():
        node.put_shard(7, bid, data)  # appended to generation 0 while it is copied
    node.delete_shards(7, [2])  # deleted meanwhile: its copy is garbage in generation 1
    node.mark_delete_shards(7, [3])  # marked meanwhile: the mark goes with the record
    shards[5] = rng.bytes(4096)
    node.put_shard(7, 5, shards[5])  # re-put meanwhile: the copy of the old record is superseded
    chaos.release("blobnode.compact_copy")
    t.join(10)
    assert chunk.gen == 1 and not t.is_alive()
    for bid in (1, 4, 5, 6, 7):
        assert node.get_shard(7, bid) == shards[bid]
    for bid, data in appended.items():
        assert node.get_shard(7, bid) == data
    for bid in (0, 2, 3):
        with pytest.raises(ShardDeleted):
            node.get_shard(7, bid)
    assert chunk.shards[3].status == bn.STATUS_MARK_DELETE and chunk.tombstones == {0, 2}
    # the new file: every record the copy took (7) + the tail (2 appended, the re-put 5), the
    # copies of 2 and of the old 5 punched in it as holes
    live = sum(record_len(m.size) for m in chunk.shards.values())
    assert chunk.holes == 2 * record_len(4096) and chunk.used == live + chunk.holes
    assert counter("compact_bytes", {"kind": "copied"}) == copied0 + chunk.used
    assert counter("compact_bytes", {"kind": "reclaimed"}) >= reclaimed0
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])  # and it is what a restart finds
    assert node._chunk(7).gen == 1 and node.get_shard(7, 21) == appended[21] and node.get_shard(7, 5) == shards[5]
    with pytest.raises(ShardDeleted):
        node.get_shard(7, 3)
    node.close()


def test_a_crash_before_the_gen_bump_leaves_the_old_generation_served(tmp_path, rng):
    node, chunk, shards = one_chunk(tmp_path, rng)
    node.delete_shards(7, [0, 1])
    chaos.arm("blobnode.compact_commit", "error(killed)", times=1)
    with pytest.raises(chaos.FailpointError):
        chunk.compact()
    orphan = chunk._gen_path(1)
    assert chunk.gen == 0 and os.path.exists(orphan)
    assert node.get_shard(7, 2) == shards[2]  # the process went on: still generation 0
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    assert node._chunk(7).gen == 0 and not os.path.exists(orphan)
    for bid in range(2, 8):
        assert node.get_shard(7, bid) == shards[bid]
    assert node._chunk(7).compact() == 2 * record_len(4096)  # and the next compaction commits
    node.close()


def test_a_chunk_closed_under_its_compaction_leaves_no_orphan(tmp_path, rng):
    node, chunk, _ = one_chunk(tmp_path, rng)
    node.delete_shards(7, [0])
    t, out = held_compaction(chunk)
    node.drop_vuid(7)  # the unit is re-homed away while its chunk is copied
    chaos.release("blobnode.compact_copy")
    t.join(10)
    assert out == {"reclaimed": 0}
    assert os.listdir(os.path.join(str(tmp_path / "d0"), "chunks")) == []
    node.close()


@pytest.mark.parametrize("max_size,picked", [(4 << 20, True), (1 << 30, False)])
def test_the_rule_picks_a_chunk_that_is_large_and_mostly_empty(tmp_path, rng, max_size, picked):
    node, chunk, _ = one_chunk(tmp_path, rng, n=10, size=300_000)  # 3 MB: large for a 4 MiB chunk only
    chunk.max_size = max_size
    node.delete_shards(7, range(7))
    assert node.compact_once() == 0  # 0.7 of it holes: not yet
    node.delete_shards(7, [7])
    total0 = counter("compact_total")
    assert (node.compact_once() > 0) == picked and counter("compact_total") == total0 + picked
    assert node.compact_once() == 0  # picked or not, the rule has nothing more to say of it
    node.close()


# -- extent files: what a filesystem that refuses the punch gets back, and when -----------


@pytest.fixture(params=["punched", "refused"])
def punch(request, monkeypatch):
    """Both filesystems: one that takes PUNCH_HOLE (this host's) and one that
    answers EOPNOTSUPP to every punch (the measuring host's 9p root)."""
    if request.param == "refused":
        monkeypatch.setattr(bn, "_punch_hole", lambda fd, offset, length: False)
    return request.param


def extent_chunk(tmp_path, rng, monkeypatch, n=30, size=300_000, extent=1 << 20, chunk_size=16 << 20):
    """One chunk cut every MiB: three records of 300,056 B an extent, the
    rest of each (148,408 B) never written."""
    monkeypatch.setattr(bn.Chunk, "EXTENT_SIZE", extent)
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    for disk in node.disks.values():
        disk.chunk_size = chunk_size
    node.create_vuid(7)
    shards = {bid: rng.bytes(size) for bid in range(n)}
    for bid, data in shards.items():
        node.put_shard(7, bid, data)
    return node, node._chunk(7), shards


def files_of(tmp_path):
    return sorted(f for f in os.listdir(tmp_path / "d0" / "chunks") if f.endswith(".data"))


def test_no_record_straddles_two_extents_and_the_skipped_end_is_nobodys(tmp_path, rng, monkeypatch):
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch)
    rec = record_len(300_000)
    assert files_of(tmp_path) == ["vuid-7.data"] + [f"vuid-7.x{k}.data" for k in range(1, 10)]
    assert all(m.offset // (1 << 20) == (m.offset + rec - 1) // (1 << 20) for m in chunk.shards.values())
    assert sorted(m.offset for m in chunk.shards.values())[:4] == [0, rec, 2 * rec, 1 << 20]
    skipped = 9 * ((1 << 20) - 3 * rec)  # nine extents were left for the next: never written, never held
    assert chunk.used == 30 * rec + skipped and chunk.holes == skipped and chunk.live == chunk.held == 30 * rec
    assert sum(os.path.getsize(tmp_path / "d0" / "chunks" / f) for f in files_of(tmp_path)) == 30 * rec
    for bid, data in shards.items():
        assert node.get_shard(7, bid) == data
    assert node.get_shard(7, 4, offset=70_000, size=100_000) == shards[4][70_000:170_000]
    with pytest.raises(bn.BlobNodeError, match="larger than an extent"):
        node.put_shard(7, 999, bytes((1 << 20) + 1))
    # the chunk is full by its datafile's length, skipped ends included: a volume's chunks fill in lockstep
    chunk.max_size = (10 << 20) + rec  # the tenth extent is full: the next record starts the eleventh
    node.put_shard(7, 30, shards[0])  # and fits to the byte
    assert chunk.shards[30].offset == 10 << 20 and chunk.used == chunk.max_size
    with pytest.raises(bn.ChunkFull):
        node.put_shard(7, 31, shards[0])
    node.close()


def test_an_extent_whose_last_record_dies_is_unlinked_and_its_bytes_come_back(tmp_path, rng, monkeypatch, punch):
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch)
    rec = record_len(300_000)
    c0 = {n: counter(n) for n in ("hole_bytes", "punched_bytes", "punch_failed", "released_bytes", "extents_dropped")}
    grew = lambda n: counter(n) - c0[n]  # noqa: E731
    used = chunk.used
    node.delete_shards(7, [0, 1])  # two of extent 0's three
    assert files_of(tmp_path)[0] == "vuid-7.data" and grew("extents_dropped") == 0
    assert grew("released_bytes") == (2 * rec if punch == "punched" else 0)
    assert chunk.held - chunk.live == (0 if punch == "punched" else 2 * rec)  # dead, and still paid for
    node.delete_shards(7, [2])  # the last: the extent goes, and on either filesystem every byte of it is back
    assert "vuid-7.data" not in files_of(tmp_path) and grew("extents_dropped") == 1
    assert grew("released_bytes") == 3 * rec == grew("hole_bytes") and chunk.held == chunk.live == 27 * rec
    node.delete_shards(7, range(3, 13))  # oldest first, as a retention policy expires: three more extents and a third
    assert files_of(tmp_path) == [f"vuid-7.x{k}.data" for k in range(4, 10)] and grew("extents_dropped") == 4
    assert grew("hole_bytes") == 13 * rec
    assert grew("released_bytes") == (13 if punch == "punched" else 12) * rec
    assert (grew("punched_bytes"), grew("punch_failed")) == ((13 * rec, 0) if punch == "punched" else (0, 13))
    assert chunk.used == used and chunk.live == 17 * rec  # a datafile's length never falls with a delete
    assert node.stats()["disks"][0]["used"] == used and node.stats()["disks"][0]["held"] == chunk.held
    node.delete_shards(7, range(27, 30))  # the LAST extent is never dropped: the datafile ends there
    assert files_of(tmp_path)[-1] == "vuid-7.x9.data" and grew("extents_dropped") == 4
    node.put_shard(7, 30, shards[0])  # it is full: the next record starts the eleventh, and the tenth may go now
    assert chunk.shards[30].offset == 10 << 20 and node.get_shard(7, 30) == shards[0]
    used = chunk.used
    for bid in range(13, 27):
        assert node.get_shard(7, bid) == shards[bid]
    for bid in (0, 12, 28):
        with pytest.raises(ShardDeleted):
            node.get_shard(7, bid)
    node.close()
    # reopened: the account is read back from the index and from what the files hold
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    again = node._chunk(7)
    assert (again.used, again.live, again.holes) == (chunk.used, chunk.live, chunk.holes)
    # (a punch leaves the blocks its ends lie in: the files hold a few KiB beside the live records)
    assert again.held - again.live <= (64 << 10 if punch == "punched" else 4 * rec) and again.held >= again.live
    for bid in range(13, 27):
        assert node.get_shard(7, bid) == shards[bid]
    node.close()


def test_the_held_gauge_is_what_the_filesystem_holds_of_every_open_chunk(tmp_path, rng, monkeypatch, punch):
    from chubaofs_tpu.utils import exporter

    def rendered():
        text = exporter.render_all()
        return float(next(l for l in text.splitlines() if l.startswith("cfs_blobnode_held_bytes ")).split()[1])

    before = rendered()
    node, chunk, _ = extent_chunk(tmp_path, rng, monkeypatch, n=6)
    rec = record_len(300_000)
    assert rendered() - before == chunk.held == 6 * rec
    node.delete_shards(7, [0, 4])
    assert rendered() - before == chunk.held == (4 if punch == "punched" else 6) * rec
    node.delete_shards(7, [1, 2])
    assert rendered() - before == chunk.held == (2 if punch == "punched" else 3) * rec  # extent 0 went whole
    node.close()
    assert rendered() == before  # a closed chunk is nobody's to count


def test_a_compaction_across_extents_copies_catches_up_and_swaps(tmp_path, rng, monkeypatch, punch):
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch, n=12)
    rec = record_len(300_000)
    punched0, refused0, holes0 = counter("punched_bytes"), counter("punch_failed"), counter("hole_bytes")
    node.delete_shards(7, [1, 4, 5, 9])  # holes in three of the four extents; none dies whole
    released0, garbage0 = counter("released_bytes"), counter("compact_bytes", {"kind": "garbage"})
    assert files_of(tmp_path) == ["vuid-7.data", "vuid-7.x1.data", "vuid-7.x2.data", "vuid-7.x3.data"]
    t, out = held_compaction(chunk)
    try:
        for bid in range(12, 17):  # appended while the copy is held: a new extent of generation 0 among them
            shards[bid] = rng.bytes(300_000)
            node.put_shard(7, bid, shards[bid])
        node.delete_shards(7, [0, 2, 12])  # two of them already copied: garbage in generation 1
        assert "vuid-7.x5.data" in files_of(tmp_path) and chunk.gen == 0
    finally:
        chaos.release("blobnode.compact_copy")
        t.join(10)
    live = [b for b in range(17) if b not in (0, 1, 2, 4, 5, 9, 12)]
    assert not t.is_alive() and chunk.gen == 1 and sorted(chunk.shards) == live
    assert all(f.startswith("vuid-7.g1.") for f in files_of(tmp_path)), files_of(tmp_path)
    for bid in live:
        assert node.get_shard(7, bid) == shards[bid]
    assert all(m.offset // (1 << 20) == (m.offset + rec - 1) // (1 << 20) for m in chunk.shards.values())
    assert chunk.live == 10 * rec and chunk.used == chunk.live + chunk.holes
    # what the filesystem holds: the live records, and the two garbage records where it refused their punch
    assert chunk.held - chunk.live == (0 if punch == "punched" else 2 * rec)
    # every byte a delete made a hole went back or is still held: 7 records died, the compaction's garbage included
    # and every dead byte ever made, the two copies the compaction wrote of records that died under it
    # included (its `garbage`), went back or is still held
    garbage = counter("compact_bytes", {"kind": "garbage"}) - garbage0
    assert garbage == 2 * rec
    assert counter("released_bytes") - released0 == (7 * rec + garbage - (chunk.held - chunk.live)) - (4 * rec if punch == "punched" else 0)
    # a record is counted when it dies, once: not again when its copy in the new generation is given back
    assert counter("hole_bytes") - holes0 == 7 * rec
    assert (counter("punched_bytes") - punched0, counter("punch_failed") - refused0) == ((7 * rec, 0) if punch == "punched" else (0, 7))
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    assert node._chunk(7).gen == 1
    for bid in live:
        assert node.get_shard(7, bid) == shards[bid]
    node.close()


def test_a_datafile_written_before_there_were_extents_is_a_long_extent_0(tmp_path, rng, monkeypatch):
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch, n=8, extent=64 << 20)  # one file, as before
    rec = record_len(300_000)
    assert files_of(tmp_path) == ["vuid-7.data"] and chunk.used == 8 * rec
    node.close()
    monkeypatch.setattr(bn.Chunk, "EXTENT_SIZE", 1 << 20)
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    chunk = node._chunk(7)
    for bid, data in shards.items():
        assert node.get_shard(7, bid) == data
    node.put_shard(7, 8, shards[0])  # past the old file's end: the extent that offset lies in
    assert chunk.shards[8].offset == 8 * rec and files_of(tmp_path) == ["vuid-7.data", "vuid-7.x2.data"]
    node.delete_shards(7, range(8))  # the old file's last record dies: it goes whole
    assert files_of(tmp_path) == ["vuid-7.x2.data"] and node.get_shard(7, 8) == shards[0]
    assert chunk.compact() > 0 and node.get_shard(7, 8) == shards[0] and files_of(tmp_path) == ["vuid-7.g1.data"]
    node.close()


def test_a_crash_after_an_extent_went_and_before_its_tombstones_replays_clean(tmp_path, rng, monkeypatch, punch):
    """Phase two punches, drops the extent and only then writes the
    tombstones: reopened in between, the index holds the bids MARK_DELETE in a
    file that is not there. They are not served, the replayed delete finishes
    them, and nothing else of the chunk is touched."""
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch, n=9)
    node.mark_delete_shards(7, [0, 1, 2])
    sound = chunk._db.write_batch

    def crash(puts=(), deletes=()):
        raise OSError("killed before the tombstones")

    chunk._db.write_batch = crash
    with pytest.raises(OSError, match="killed"):
        node.delete_shards(7, [0, 1, 2])
    chunk._db.write_batch = sound
    assert "vuid-7.data" not in files_of(tmp_path)
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    for bid in (0, 1, 2):
        with pytest.raises(ShardDeleted):
            node.get_shard(7, bid)
    for bid in range(3, 9):
        assert node.get_shard(7, bid) == shards[bid]
    assert node.delete_shards(7, [0, 1, 2]) == 3 and node._chunk(7).tombstones == {0, 1, 2}
    assert node._chunk(7).compact() >= 0
    for bid in range(3, 9):
        assert node.get_shard(7, bid) == shards[bid]
    node.close()


def test_the_rule_also_picks_a_chunk_that_holds_more_dead_bytes_than_live(tmp_path, rng, monkeypatch, punch):
    """On a filesystem that refuses the punch a dead record is space still to
    reclaim: once a chunk holds more dead bytes than live ones (what its dying
    extents have not given back) a compaction copies at most a byte for every
    byte it frees. Where the punch is taken nothing dead is held."""
    monkeypatch.setattr(BlobNode, "COMPACT_MIN_DEAD_HELD", 1 << 20)
    node, chunk, shards = extent_chunk(tmp_path, rng, monkeypatch, n=12, extent=64 << 20)  # one extent: none dies whole
    rec = record_len(300_000)
    node.delete_shards(7, range(1, 6))
    assert list(node.compaction_candidates()) == []  # five dead, seven live
    node.delete_shards(7, [6, 7])
    picked = punch == "refused"
    assert list(node.compaction_candidates()) == ([chunk] if picked else []) and chunk.held - chunk.live == picked * 7 * rec
    released0, copied0 = counter("released_bytes"), counter("compact_bytes", {"kind": "copied"})
    assert (node.compact_once() > 0) == picked
    assert counter("released_bytes") - released0 == picked * 7 * rec  # what the compaction gave back, once
    assert counter("compact_bytes", {"kind": "copied"}) - copied0 == picked * 5 * rec <= picked * 7 * rec
    assert chunk.held == chunk.live == 5 * rec and list(node.compaction_candidates()) == []
    for bid in (0, 8, 9, 10, 11):
        assert node.get_shard(7, bid) == shards[bid]
    node.close()


def test_where_the_punch_is_refused_a_clusters_space_returns_an_extent_at_a_time(tmp_path, rng, monkeypatch, punch):
    """The cell in small: oldest first, through the gateway's DELETE and the
    deleter. Every byte of a record made a hole is given back or still held,
    and what is still held is less than an extent a chunk."""
    monkeypatch.setattr(bn.Chunk, "EXTENT_SIZE", 1 << 20)
    c = MiniCluster(str(tmp_path), **LAYOUT)
    try:
        model = reference_expire.Store(CONFIG)
        locs = []
        for _ in range(16):
            data = rng.bytes(3_000_000)  # EC12P4, a record of 250,048 B a unit: four an extent
            locs.append(c.access.put(data))
            model.put(locs[-1].to_json(), data)
        chunks = [ch for n in c.nodes.values() for d in n.disks.values() for ch in d.chunks.values() if ch.used]
        holes0, released0 = counter("hole_bytes"), counter("released_bytes")
        before = model.live_stored_bytes()
        for loc in locs[:11]:
            c.access.delete(loc)
            model.delete(loc.to_json())
        assert c.run_background_once()["deletes"] == 11
        made = counter("hole_bytes") - holes0
        dead_held = sum(ch.held - ch.live for ch in chunks)
        assert made == before - model.live_stored_bytes() and sum(ch.live for ch in chunks) == model.live_stored_bytes()
        assert counter("released_bytes") - released0 == made - dead_held
        if punch == "punched":
            assert dead_held == 0
        else:
            assert 0 < dead_held < len(chunks) * (1 << 20) and counter("extents_dropped") > 0
            assert made - dead_held >= 0.5 * made  # most of it is back, with no compaction
        for loc in locs[11:]:
            assert_shards_are_the_reference(c, model, loc.to_json(), loc)
    finally:
        c.close()


# -- the deleter's own worker -------------------------------------------------------------


def test_the_deleter_drains_a_backlog_without_a_tick(cluster, rng):
    """N > 64 messages (the old deleter's batch a tick), no tick at all: the
    topic wakes the worker, as in the daemon."""
    c = cluster
    locs = [c.access.put(rng.bytes(3000)) for _ in range(70)]
    c.scheduler.switches.set(SWITCH_BLOB_DELETE, False)
    c.reclaimer.follow_topic()
    ok0 = counter("delete_blobs", {"result": "ok"}, "scheduler")
    for loc in locs:
        c.access.delete(loc)
    time.sleep(0.3)
    assert c.proxy.delete_backlog() == 70 and gauge("delete_backlog") == 70  # held: nothing applied
    assert c.access.get(locs[0]) is not None
    c.scheduler.switches.set(SWITCH_BLOB_DELETE, True)
    c.reclaimer.kick()  # what the release of a switch does; no tick anywhere in this test
    deadline = time.monotonic() + 20
    while c.proxy.delete_backlog() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert c.proxy.delete_backlog() == 0
    c.reclaimer.wait_idle()
    assert gauge("delete_backlog") == 0
    assert counter("delete_blobs", {"result": "ok"}, "scheduler") == ok0 + 70
    for loc in locs:
        with pytest.raises(BlobDeleted):
            c.access.get(loc)
    names = {t.name for t in threading.enumerate()}
    assert "reclaim-worker" in names and any(n.startswith("reclaim-io") for n in names)


def test_a_served_delete_is_applied_with_no_tick_to_wait_for(cluster, rng):
    c = cluster
    c.reclaimer.follow_topic()
    gw = AccessGateway(c.access)
    try:
        loc = c.access.put(rng.bytes(600_000))
        applies, served = stage_count("deleter.apply"), stage_count("access.delete")
        calls = registry("access").summary("delete").snapshot()["count"]
        assert http_delete(gw.addr, loc.to_json()) == 200
        deadline = time.monotonic() + 10
        while http_get(gw.addr, loc.to_json())[0] != 404 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert http_get(gw.addr, loc.to_json())[0] == 404
        c.reclaimer.wait_idle()
        assert stage_count("deleter.apply") == applies + 1 and stage_count("access.delete") == served + 1
        assert registry("access").summary("delete").snapshot()["count"] == calls + 1
    finally:
        gw.stop()


def test_the_tick_waits_for_neither_the_deleter_nor_a_compaction(cluster, rng):
    c = cluster
    loc = c.access.put(rng.bytes(3_000_000))
    c.access.delete(loc)
    entered, go = threading.Event(), threading.Event()
    sound = c.scheduler._drop_hot_copy
    c.scheduler._drop_hot_copy = lambda *a: (entered.set(), go.wait(60), sound(*a))[2]  # a drain held mid-way
    try:
        t0 = time.monotonic()
        stats = c.background_tick()  # what the daemon's ticker runs, under the runner lock
        assert time.monotonic() - t0 < 5.0 and stats["deletes"] == 0 and stats["compacted_bytes"] == 0
        assert entered.wait(10) and c.proxy.delete_backlog() == 1  # the reclaim worker has it, and is held
        t0 = time.monotonic()
        assert c.background_tick()["deletes"] == 0 and time.monotonic() - t0 < 5.0  # a second tick returns too
        assert c.access.get(loc) is not None  # nothing applied yet
    finally:
        go.set()
    assert c.run_background_once()["tasks_ran"] == 0 and c.proxy.delete_backlog() == 0
    with pytest.raises(BlobDeleted):
        c.access.get(loc)


def test_the_counters_move_by_the_models_numbers(cluster, rng):
    c = cluster
    model = reference_expire.Store(CONFIG)
    locs = []
    for size in SIZES:
        data = rng.bytes(size)
        locs.append(c.access.put(data))
        model.put(locs[-1].to_json(), data)
    held = model.live_stored_bytes()
    before = {k: counter(*k) for k in (("shard_delete",), ("hole_bytes",), ("punched_bytes",), ("punch_failed",),
                                       ("released_bytes",))}
    ok0, batches = counter("delete_blobs", {"result": "ok"}, "scheduler"), stage_count("deleter.batch")
    for loc in locs[1:]:
        c.access.delete(loc)
        model.delete(loc.to_json())
    blobs = sum(len(loc.blobs) for loc in locs[1:])
    assert c.proxy.delete_backlog() == blobs == 4
    assert c.run_background_once()["deletes"] == blobs
    assert counter("delete_blobs", {"result": "ok"}, "scheduler") == ok0 + blobs
    assert counter("delete_blobs", {"result": "partial"}, "scheduler") >= 0 and stage_count("deleter.batch") == batches + 1
    assert counter("shard_delete") - before[("shard_delete",)] == 9 + 16 + 2 * 16  # every unit of every blob
    released = held - model.live_stored_bytes()
    assert counter("hole_bytes") - before[("hole_bytes",)] == released
    if counter("punch_failed") == before[("punch_failed",)]:  # the filesystem punches holes
        assert counter("punched_bytes") - before[("punched_bytes",)] == released
        assert counter("released_bytes") - before[("released_bytes",)] == released
    assert live_bytes(c) == model.live_stored_bytes()
    for name in ("cfs_blobnode_shard_delete", "cfs_blobnode_hole_bytes", "cfs_blobnode_punched_bytes", "cfs_blobnode_punch_failed",
                 "cfs_blobnode_released_bytes", "cfs_blobnode_extents_dropped", "cfs_blobnode_held_bytes",
                 "cfs_blobnode_compact_total", 'cfs_blobnode_compact_bytes{kind="copied"}',
                 'cfs_blobnode_compact_bytes{kind="reclaimed"}', 'cfs_blobnode_compact_bytes{kind="garbage"}',
                 "cfs_scheduler_delete_backlog",
                 'cfs_scheduler_delete_blobs{result="ok"}', 'cfs_scheduler_delete_blobs{result="partial"}',
                 "cfs_access_delete_count", "cfs_access_delete_errors",
                 'cfs_trace_stage_seconds_count{stage="chunk.compact_swap"}',
                 'cfs_trace_stage_seconds_count{stage="deleter.apply"}'):
        from chubaofs_tpu.utils import exporter

        assert any(line.startswith(name + " ") for line in exporter.render_all().splitlines()), name


def test_a_unit_that_cannot_take_the_delete_makes_the_blob_partial(cluster, rng):
    """A dark node's unit keeps its shard: the blob counts `partial`, the
    message is consumed all the same, and the inspector finishes it later."""
    c = cluster
    loc = c.access.put(rng.bytes(3_000_000))
    unit = c.cm.get_volume(loc.blobs[0].vid).units[2]
    dark = c.nodes.pop(unit.node_id)
    try:
        partial0 = counter("delete_blobs", {"result": "partial"}, "scheduler")
        c.access.delete(loc)
        assert c.scheduler.run_deleter() == 1
        assert counter("delete_blobs", {"result": "partial"}, "scheduler") == partial0 + 1
        assert c.proxy.topics[TOPIC_BLOB_DELETE].lag("deleter") == 0
        assert dark.get_shard(unit.vuid, loc.blobs[0].bid)  # still there
    finally:
        c.nodes[unit.node_id] = dark
    with pytest.raises(BlobDeleted):
        c.access.get(loc)


def test_the_reclaim_worker_compacts_what_the_rule_picks_one_chunk_between_two_drains(cluster, rng):
    """A volume's sixteen chunks cross the rule together; the worker compacts
    them one at a time and looks at the topic in between."""
    c = cluster
    locs = [c.access.put(rng.bytes(3_000_000)) for _ in range(12)]  # one volume pair, ~1.5 MB a chunk
    chunks = {id(ch): ch for loc in locs for u in c.cm.get_volume(loc.blobs[0].vid).units
              for ch in [c.nodes[u.node_id]._chunk(u.vuid)]}
    for ch in chunks.values():
        ch.max_size = 2 << 20  # "large" is then 1 MiB: these chunks are
    for loc in locs[:10]:
        c.access.delete(loc)
    drains = []
    sound = c.scheduler.run_deleter
    c.scheduler.run_deleter = lambda **kw: (drains.append(sum(ch.gen for ch in chunks.values())), sound(**kw))[1]
    total0 = counter("compact_total")
    stats = c.run_background_once()
    picked = [ch for ch in chunks.values() if ch.gen == 1]
    assert stats["deletes"] == 10 and stats["compacted_bytes"] > 0 and len(picked) >= 16
    assert counter("compact_total") == total0 + len(picked) and all(ch.holes == 0 for ch in picked)
    # the topic was looked at after every single compaction: the generations seen there rise by one
    assert drains[1:] == list(range(len(picked) + 1))
    for loc in locs[10:]:
        assert len(c.access.get(loc)) == 3_000_000
    node = next(iter(c.nodes.values()))
    assert not any(list(n.compaction_candidates()) for n in c.nodes.values())  # the rule picks nothing more


def test_the_inspector_reports_nothing_for_a_blob_deleted_under_its_sweep(cluster, rng):
    """The deleter runs beside the inspector now: a blob whose units were all
    listed live and which is deleted before its CRC reads is not damage."""
    c = cluster
    loc = c.access.put(rng.bytes(3_000_000))
    vol = c.cm.get_volume(loc.blobs[0].vid)
    first = c.nodes[vol.units[0].node_id]
    sound = first.get_shard
    done = []

    def deleted_just_before_the_read(vuid, bid, *a, **kw):
        if not done:
            done.append(c.access.delete(loc))
            assert c.scheduler.run_deleter() == 1
        return sound(vuid, bid, *a, **kw)

    first.get_shard = deleted_just_before_the_read
    repair = c.proxy.topics[TOPIC_SHARD_REPAIR]
    before = repair.lag("scheduler")
    assert c.scheduler.inspect_volumes(max_volumes=100) == 0 and done
    assert repair.lag("scheduler") == before


def test_writers_deleters_readers_and_compactions_of_one_chunk_lose_nothing(tmp_path, rng):
    """A time-bounded stress of the state a chunk shares between the write
    workers, the reclaim pool, readers and its compaction: more threads than
    cores, a short switch interval. A lost update would break one of: every
    live shard reads back its bytes, every deleted one is ShardDeleted, the
    file is its live records plus its holes, and a restart finds the same."""
    import sys

    node, chunk, _ = one_chunk(tmp_path, rng, n=0)
    payloads = {w: rng.bytes(2000 + 37 * w) for w in range(6)}
    live: dict[int, int] = {}  # bid -> the writer whose bytes it holds
    deleted: set[int] = set()
    lock, stop, errors = threading.Lock(), threading.Event(), []

    def guarded(fn):
        def run(*a):
            try:
                while not stop.is_set():
                    fn(*a)
            except Exception as e:  # noqa: BLE001 - the stress reports whatever broke
                errors.append(f"{fn.__name__}: {type(e).__name__}: {e}")
        return run

    nxt = iter(range(1 << 30))

    def write(w):
        with lock:
            bid = next(nxt)
        node.put_shard(7, bid, payloads[w])
        with lock:
            live[bid] = w

    def delete():
        with lock:
            bids = list(live)[:5]
            for b in bids:
                del live[b]
        if bids:
            node.mark_delete_shards(7, bids)
            assert node.delete_shards(7, bids) == len(bids)
            with lock:
                deleted.update(bids)
        else:
            time.sleep(0.001)

    def read():
        with lock:
            pick = next(iter(live.items()), None)
        if pick is not None:
            try:
                assert node.get_shard(7, pick[0]) == payloads[pick[1]]
            except ShardDeleted:
                pass  # deleted between the pick and the read

    def compact():
        chunk.compact()

    threads = [threading.Thread(target=guarded(write), args=(w,)) for w in range(6)]
    threads += [threading.Thread(target=guarded(f)) for f in (delete, delete, read, read, read, compact)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(20)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads), errors[:3]
    assert chunk.gen >= 1 and len(live) + len(deleted) > 50

    def holds_everything(n, ch):
        assert set(ch.shards) == set(live) and ch.tombstones >= deleted
        for bid, w in live.items():
            assert n.get_shard(7, bid) == payloads[w]
        for bid in list(deleted)[:50]:
            with pytest.raises(ShardDeleted):
                n.get_shard(7, bid)
        assert ch.used == sum(record_len(m.size) for m in ch.shards.values()) + ch.holes

    holds_everything(node, chunk)
    node.close()
    node = BlobNode(node_id=1, disk_roots=[str(tmp_path / "d0")])
    holds_everything(node, node._chunk(7))
    node.close()
