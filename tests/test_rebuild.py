"""The rebuild of a lost node under reads: configuration az1-ec12p4-rebuild
(benchmark/configs), the benchmark cell az1.get16m-rebuild.

A node is lost for good, the operator declares its disks broken
(POST /admin/disk/set), and the scheduler's disk repair rebuilds every stripe
position they held by decode from N survivors onto a disk that holds no unit
of the volume, re-homes the unit in the cluster manager and drops the disk,
while clients keep reading. Held here, on the CPU at small sizes and on one
stripe at the published shard width: the rebuilt rows (data, parity, LRC local
parity) equal the plain reference (benchmark/reference_rebuild.py); served GETs
are byte-equal before, between and after every commit; the read plan follows a
re-homed unit; the rebuild compiles nothing beyond the degraded GET's warmed
set; the daemon's tick never waits for it; stop() mid-rebuild returns and a
restart finishes the task."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from chubaofs_tpu import chaos
from chubaofs_tpu.blobstore import scheduler as sched_mod
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.clustermgr import DISK_BROKEN, DISK_DROPPED, DISK_NORMAL
from chubaofs_tpu.codec import service as codec_service
from chubaofs_tpu.codec.codemode import CodeMode
from chubaofs_tpu.ops import device, rs
from chubaofs_tpu.utils.exporter import registry

from test_azdown import _json, _load

MiB = 1 << 20
reference = _load("reference")
reference_rebuild = _load("reference_rebuild")
CONFIG = _json("configs", "az1-ec12p4-rebuild.json")
TRAFFIC = _json("traffic", "get16m-rebuild.json")
CODE = CONFIG["code"]
MODES = dict(CONFIG["modes"], EC6P3L3=_json("configs", "az3-ec6p3l3.json")["modes"]["EC6P3L3"])
LAYOUT = dict(n_nodes=CONFIG["layout"]["nodes"], disks_per_node=CONFIG["layout"]["disks_per_node"])


def counter(name, labels=None, role="scheduler"):
    return registry(role).counter(name, labels).value


def plans():
    return {p: counter("read_plan_total", {"plan": p}, "access") for p in ("direct", "one_round", "two_round")}


def kill(cluster, node):
    """Permanent loss, as benchmark/deploy.py node_down and chaos/scheduler.py _kill do."""
    cluster.nodes.pop(node).close()


def disks_of(cluster, node):
    return [d.disk_id for d in cluster.cm.disks.values() if d.node_id == node]


# -- the configuration, its traffic file and the benchmark's index say one thing ----


def test_configuration_is_az1_with_node_1_rebuilt():
    base = _json("configs", "az1-ec12p4.json")
    for key in ("layout", "policies", "modes", "max_blob_size", "cache_plane", "code", "scale"):
        assert CONFIG[key] == base[key], key
    assert "task_switches_off" not in CONFIG and "switches_off" not in TRAFFIC
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert all(1 <= len(e["why"]) <= 200 for g in ("configs", "workloads") for e in bench[g])
    # appended, not inserted: right after what the benchmark held before PR 36
    assert [c["name"] for c in bench["configs"]].index(CONFIG["name"]) == 4
    assert [w["name"] for w in bench["workloads"]].index("az1.get16m-rebuild") == 6
    cell = bench["workloads"][6]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "az1.get16m-rebuild", CONFIG["name"], "get16m-rebuild", 1)
    p = TRAFFIC["params"]
    assert TRAFFIC["nodes_down"] == p["declare_broken_nodes"] == CONFIG["failure"]["nodes"] == [1]
    assert (p["streams"], p["object_bytes"], p["objects"]) == (
        CONFIG["assumed"]["reader_streams"], CONFIG["assumed"]["object_bytes"], CONFIG["assumed"]["objects"])
    assert p["objects"] >= 256 and TRAFFIC["kind"] == "closed_get_rebuild"
    assert TRAFFIC["verify"]["counter_delta_min"]["cfs_scheduler_repaired_shards"] == 300
    # a unit is re-homed inside the window, so the compared bodies hold rebuilt rows
    assert TRAFFIC["verify"]["counter_delta_min"]["cfs_scheduler_rebuild_units_committed"] == 1
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
                if "workloads" not in m or cell["name"] in m["workloads"]}
    assert {"get_MBps", "setup_s", "rebuild_shards_per_s", "rebuild_read_amp", "rebuild_gather_ms",
            "rebuild_decode_wait_ms", "rebuild_write_back_ms", "rebuild_units_committed",
            "rebuild_job_share_pct", "get_gf_kernel_roofline", "get_codec_jobs_per_batch"} <= reported


# -- the rows the worker rebuilds are the reference's, at every stripe position --------


@pytest.fixture(scope="module")
def az1(tmp_path_factory):
    c = MiniCluster(str(tmp_path_factory.mktemp("az1")), **LAYOUT)
    yield c
    c.close()


@pytest.fixture(scope="module")
def az3(tmp_path_factory):
    c = MiniCluster(str(tmp_path_factory.mktemp("az3")), azs=3, **LAYOUT)
    yield c
    c.close()


def rebuilt_by_the_worker(c, mode, size, index):
    """PUT one blob, lose position ``index``, and ask the worker's rebuild
    path (gather, then the row) for it: (the row, the reference stripe)."""
    data = np.random.default_rng([36, size, index]).bytes(size)
    loc = c.access.put(data, code_mode=CodeMode[mode])
    assert len(loc.blobs) == 1
    blob = loc.blobs[0]
    vol = c.cm.get_volume(blob.vid)
    unit = vol.units[index]
    c.nodes[unit.node_id].lose_shard(unit.vuid, blob.bid)
    w, t = c.worker, vol.tactic()
    row = w._submit_row(vol, t, unit, blob.bid, w._gather_for_unit(vol, t, unit, blob.bid))
    if not isinstance(row, bytes):
        row = row.result()
    return row, reference.encode(data, MODES[mode], CODE)


def check_row(c, mode, size, index):
    row, stripe = rebuilt_by_the_worker(c, mode, size, index)
    left = [None if p == index else s.tobytes() for p, s in enumerate(stripe)]
    assert row == reference_rebuild.rebuilt_row(left, index, MODES[mode], CODE), "worker != reference_rebuild"
    assert row == stripe[index].tobytes(), "rebuilt row != the reference stripe's"


@pytest.mark.parametrize("index", range(16))
def test_ec12p4_row_equals_reference_at_every_position(az1, index):
    check_row(az1, "EC12P4", 300_000 + index, index)


@pytest.mark.parametrize("index", range(12))
def test_lrc_row_equals_reference_at_every_position(az3, index):
    """EC6P3L3 through the same code: data, global parity and (9-11) the three
    AZs' local parities, re-encoded from the AZ's local stripe."""
    check_row(az3, "EC6P3L3", 200_000 + index, index)


@pytest.mark.parametrize("index", [0, 13])
def test_row_at_the_published_shard_width(az1, index):
    row, _ = rebuilt_by_the_worker(az1, "EC12P4", 4 * MiB, index)
    assert len(row) == 349_526
    check_row(az1, "EC12P4", 4 * MiB, index)


def test_lrc_local_parity_with_a_hole_in_its_local_stripe(az3):
    """The local stripe of AZ 0 lacks a data row too: decoded first, from N
    global survivors, then the local parity is re-encoded."""
    c, mode = az3, "EC6P3L3"
    data = np.random.default_rng(3636).bytes(150_000)
    loc = c.access.put(data, code_mode=CodeMode[mode])
    blob, vol = loc.blobs[0], c.cm.get_volume(loc.blobs[0].vid)
    for i in (0, 9):
        c.nodes[vol.units[i].node_id].lose_shard(vol.units[i].vuid, blob.bid)
    w, t, unit = c.worker, vol.tactic(), vol.units[9]
    row = w._submit_row(vol, t, unit, blob.bid, w._gather_for_unit(vol, t, unit, blob.bid))
    stripe = reference.encode(data, MODES[mode], CODE)
    left = [None if p in (0, 9) else s.tobytes() for p, s in enumerate(stripe)]
    assert row == reference_rebuild.rebuilt_row(left, 9, MODES[mode], CODE) == stripe[9].tobytes()


def test_reference_rebuild_refuses_too_few_survivors():
    stripe = reference.encode(b"x" * 50_000, MODES["EC12P4"], CODE)
    left = [None if p < 5 else s.tobytes() for p, s in enumerate(stripe)]
    with pytest.raises(ValueError):
        reference_rebuild.rebuilt_row(left, 0, MODES["EC12P4"], CODE)


# -- lose a node, declare it, rebuild under reads: the whole path ---------------------


class Wounded:
    """A 9 x 2 cluster with 5 MiB objects (two blobs each: 4 MiB and 1 MiB,
    EC12P4), the same data in the store model, and one node lost for good."""

    def __init__(self, root, node):
        self.cluster = c = MiniCluster(root, **LAYOUT)
        self.node = node
        self.objects = []
        for i in range(3):
            data = np.random.default_rng([36, 5, i]).bytes(5 * MiB)
            self.objects.append((c.access.put(data), data))
        self.model = reference_rebuild.Store(
            {d.disk_id: d.node_id for d in c.cm.disks.values()}, MODES, CODE)
        for vol in c.cm.volumes.values():
            self.model.add_volume(vol.vid, CodeMode(vol.code_mode).name, [u.disk_id for u in vol.units])
        for loc, data in self.objects:
            off = 0
            for b in loc.blobs:
                self.model.put(b.vid, b.bid, data[off: off + b.size])
                off += b.size
        self.held = [(v.vid, u.index) for v in c.cm.volumes.values() for u in v.units if u.node_id == node]
        kill(c, node)
        self.model.lose_node(node)

    def declare(self):
        for d in disks_of(self.cluster, self.node):
            self.cluster.cm.set_disk_status(d, DISK_BROKEN, reason="operator")

    def read_all(self):
        for loc, data in self.objects:
            assert self.cluster.access.get(loc) == data


@pytest.fixture(params=[1, 7], ids=["node1-data-units", "node7-parity-units"])
def wounded(request, tmp_path):
    w = Wounded(str(tmp_path), request.param)
    yield w
    w.cluster.close()


def test_rebuild_under_reads_keeps_every_guarantee(wounded):
    """Every GET byte-equal before, between and after every commit; every
    rebuilt shard equals the reference stripe's row (node 1 holds data units,
    node 7 parity units); every re-homed unit lies on a NORMAL disk that holds
    no other unit of its volume; the disks are DROPPED only when all their
    units are committed; the rebuild read N survivors a rebuilt shard."""
    c, w = wounded.cluster, wounded.cluster.worker
    assert wounded.held and {i < 12 for _, i in wounded.held} == {wounded.node == 1}
    wounded.read_all()
    sound, commits = w._commit_unit, []

    def commit(prep, source_disk_id):
        wounded.read_all()  # rows are in the new chunk, the unit not yet re-homed
        assert c.cm.disk_status(source_disk_id) == DISK_BROKEN
        sound(prep, source_disk_id)
        commits.append((prep["vol"].vid, prep["unit"].index))
        wounded.read_all()  # the next read of the unit finds it whole

    w._commit_unit = commit
    shards0, read0, written0 = counter("repaired_shards"), counter("rebuild_bytes", {"kind": "read"}), \
        counter("rebuild_bytes", {"kind": "written"})
    wounded.declare()
    stats = c.run_background_once()
    assert stats["disk_tasks"] == 2 and stats["tasks_ran"] >= 2
    assert sorted(commits) == sorted(wounded.held)
    assert all(c.cm.disk_status(d) == DISK_DROPPED for d in disks_of(c, wounded.node))
    wounded.read_all()
    # every rebuilt shard, data and parity, against the store model's rebuild
    rebuilt = wounded.model.rebuild()
    assert rebuilt == counter("repaired_shards") - shards0 == 6 * len(wounded.held) // 2
    for vid, pos in wounded.held:
        unit = c.cm.get_volume(vid).units[pos]
        assert unit.epoch == 2 and unit.node_id in c.nodes
        for (v, p, bid), want in wounded.model.shards.items():
            if (v, p) == (vid, pos):
                assert c.nodes[unit.node_id].get_shard(unit.vuid, bid) == want, (vid, pos, bid)
    # placement: the program's map and the model's both keep the guarantees
    status = {d.disk_id: d.status for d in c.cm.disks.values()}
    placed = {v.vid: [u.disk_id for u in v.units] for v in c.cm.volumes.values()}
    assert reference_rebuild.placement_violations(placed, status) == []
    assert wounded.model.violations() == []
    # N survivors a rebuilt shard, no more
    assert counter("rebuild_bytes", {"kind": "read"}) - read0 == 12 * (
        counter("rebuild_bytes", {"kind": "written"}) - written0)


def test_placement_violations_are_found():
    status = {1: "normal", 2: "normal", 3: "dropped", 4: "broken"}
    bad = reference_rebuild.placement_violations({7: [1, 1, 2], 8: [3, 2, 4]}, status)
    assert len(bad) == 3 and "2 units on disk 1" in bad[0] and "dropped" in bad[1] and "broken" in bad[2]


def test_read_plan_follows_a_healed_volume(tmp_path):
    """one_round while a data unit of the blob is dark; direct once the rebuild
    has re-homed them, with no read handed to the dead node and no decode."""
    w = Wounded(str(tmp_path), 1)
    try:
        c = w.cluster
        before = plans()
        w.read_all()
        mid = plans()
        assert mid["one_round"] - before["one_round"] == 6 and mid["direct"] == before["direct"]
        w.declare()
        c.run_background_once()
        unrouted0 = counter("read_fail", {"reason": "no_node"}, "access")
        decoded0 = counter("read_bytes", {"kind": "decoded"}, "access")
        w.read_all()
        after = plans()
        assert after["direct"] - mid["direct"] == 6
        assert (after["one_round"], after["two_round"]) == (mid["one_round"], mid["two_round"])
        assert counter("read_fail", {"reason": "no_node"}, "access") == unrouted0
        assert counter("read_bytes", {"kind": "decoded"}, "access") == decoded0
    finally:
        c.close()


def test_rebuild_compiles_nothing_beyond_the_degraded_gets_warmed_set(tmp_path):
    """What benchmark/deploy.py warm_decode compiles (decode_rows at the rows
    the dark node leaves wanted, every batch count) is all the rebuild and the
    readers beside it run: one-row decodes are the two-row program."""
    device._install_compile_counters()
    w = Wounded(str(tmp_path), 1)
    try:
        c = w.cluster
        codec = c.codec
        keep = (codec.max_batch, codec.max_wait)
        codec.max_wait = 5.0
        try:
            for loc, _ in w.objects[:1]:
                for b in loc.blobs:
                    vol = c.cm.get_volume(b.vid)
                    t = vol.tactic()
                    want = [u.index for u in vol.units if u.index < t.N and u.node_id not in c.nodes]
                    present = [u.index for u in vol.units if u.node_id in c.nodes][: t.N]
                    assert len(want) == 2
                    surv = np.zeros((t.N, t.shard_size(b.size)), np.uint8)
                    for count in range(1, 13):
                        codec.max_batch = count
                        for f in [codec.decode_rows(t.N, t.M, present, surv, want) for _ in range(count)]:
                            f.result()
        finally:
            codec.max_batch, codec.max_wait = keep
        compiled = counter("compile_total", role="codec")
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                w.read_all()

        th = threading.Thread(target=reader, name="reader")
        th.start()
        try:
            w.declare()
            c.run_background_once()
        finally:
            stop.set()
            th.join()
        w.read_all()
        assert all(c.cm.disk_status(d) == DISK_DROPPED for d in disks_of(c, 1))
        assert counter("compile_total", role="codec") == compiled
    finally:
        c.close()


@pytest.mark.parametrize("first, then, shapes", [
    ([0, 1], [1], [(16, 96), (16, 96)]),  # a wider family has run: the one row rides it
    ([1], [0], [(8, 96), (8, 96)]),  # nothing wider (one disk lost): one row, exact
    ([1], [0, 1], [(8, 96), (16, 96)]),  # a wider count is never cut down to a narrower one
], ids=["rides-the-wider-family", "exact-where-nothing-wider-ran", "wider-after-narrower"])
def test_a_decode_rides_a_resident_wider_program_and_never_pays_for_one_that_is_not(monkeypatch, first, then, shapes):
    seen = []
    sound = rs.gf_matmul_hostbatch
    monkeypatch.setattr(rs, "gf_matmul_hostbatch", lambda bits, shards: (seen.append(bits.shape), sound(bits, shards))[1])
    svc = codec_service.CodecService()
    try:
        surv = np.random.default_rng(36).integers(0, 256, (12, 5000), dtype=np.uint8)
        present = list(range(2, 14))
        both = svc.decode_rows(12, 4, present, surv, [0, 1]).result()
        seen.clear()
        svc._decode_rows_run.clear()
        for want in (first, then):
            got = svc.decode_rows(12, 4, present, surv, want).result()
            assert got.shape == (len(want), 5000) and np.array_equal(got, both[want])
        assert seen == shapes
    finally:
        svc.close()


# -- deletes beside the rebuild ---------------------------------------------------------


@pytest.mark.parametrize("when", ["before_its_gather", "after_its_row_is_written", "under_the_re_home"])
def test_a_delete_during_the_rebuild_neither_fails_it_nor_leaves_a_shard(tmp_path, when):
    """The deleter runs beside the worker's thread. A blob deleted after the
    unit's bids were listed: its stripe cannot be gathered (skipped, not the
    task's failure); or its row is already in the new chunk (deleted there at
    the commit); or the delete punches the old units while the unit is being
    re-homed (seen by the pass after the re-home)."""
    w = Wounded(str(tmp_path), 1)
    try:
        c, worker = w.cluster, w.cluster.worker
        (gone, _), kept = w.objects[0], w.objects[1:]
        done = []

        def delete_once():
            if not done:
                done.append(c.access.delete(gone))
                assert c.scheduler.run_deleter() == len(gone.blobs)

        hook = {"before_its_gather": (worker, "_rebuild_rows"), "after_its_row_is_written": (worker, "_commit_unit"),
                "under_the_re_home": (c.cm, "update_volume_unit")}[when]
        sound = getattr(*hook)
        setattr(*hook, lambda *a, **k: (delete_once(), sound(*a, **k))[1])
        w.declare()
        stats = c.run_background_once()
        assert done and stats["tasks_ran"] >= 2
        assert all(c.cm.disk_status(d) == DISK_DROPPED for d in disks_of(c, 1))
        assert not [t for t in c.scheduler.tasks() if t.state == sched_mod.TASK_FAILED]
        for vid, pos in w.held:
            unit = c.cm.get_volume(vid).units[pos]
            node = c.nodes[unit.node_id]
            live = {m.bid for m in node.list_shards(unit.vuid)}
            for b in gone.blobs:
                if b.vid == vid:
                    assert b.bid not in live and node.has_tombstone(unit.vuid, b.bid), (when, vid, pos, b.bid)
        for loc, data in kept:
            assert c.access.get(loc) == data
    finally:
        c.close()


def test_two_declarations_at_once_make_one_task_a_disk(az1):
    """check_disks finds a broken disk taskless and makes its task under one
    lock: the ticker and the operator's HTTP thread cannot both make one."""
    c = az1
    disk = disks_of(c, 8)[0]
    c.cm.set_disk_status(disk, DISK_BROKEN, reason="operator")
    try:
        gate = threading.Barrier(8)
        made = []

        def declare():
            gate.wait()
            made.extend(c.scheduler.check_disks())

        threads = [threading.Thread(target=declare) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(made) == 1
        assert len([t for t in c.scheduler.tasks(sched_mod.KIND_DISK_REPAIR) if t.disk_id == disk]) == 1
    finally:
        with c.scheduler._lock:
            for t in made:
                c.scheduler._tasks.pop(t.task_id, None)
        c.cm.set_disk_status(disk, DISK_NORMAL)


# -- the scheduler's side: who owns a broken disk's shards, and whose thread runs the task --


def test_a_broken_disks_shards_are_the_disk_repairs_not_a_shard_repair_task(az1):
    c = az1
    loc = c.access.put(np.random.default_rng(361).bytes(200_000), code_mode=CodeMode.EC12P4)
    blob, vol = loc.blobs[0], c.cm.get_volume(loc.blobs[0].vid)
    c.scheduler.poll_repair_topic(max_msgs=10_000)
    open0 = len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR))
    c.cm.set_disk_status(vol.units[3].disk_id, DISK_BROKEN, reason="operator")
    try:
        c.proxy.send_shard_repair(vol.vid, blob.bid, [3], "get_miss")
        c.scheduler.poll_repair_topic()
        assert len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR)) == open0
        c.proxy.send_shard_repair(vol.vid, blob.bid, [3, 4], "get_miss")  # 4 is on a NORMAL disk
        c.scheduler.poll_repair_topic()
        assert len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR)) == open0 + 1
    finally:
        c.cm.set_disk_status(vol.units[3].disk_id, DISK_NORMAL, reason="test")
        while c.worker.run_once():
            pass


def test_a_report_that_waited_while_its_unit_was_re_homed_is_no_task(tmp_path):
    """Degraded GETs report every dark position, faster than a tick drains the
    topic: a report made against the unit's old epoch and polled after the
    rebuild re-homed it names a unit that is whole, and makes no task; the
    position it names beside it that is still on a broken disk stays the disk
    repair's; a report against the unit as it stands is a task as before."""
    w = Wounded(str(tmp_path), 1)
    try:
        c = w.cluster
        (vid, first), second = w.held[0], next(p for v, p in w.held[1:] if v == w.held[0][0])
        bid = next(b.bid for loc, _ in w.objects for b in loc.blobs if b.vid == vid)
        c.proxy.send_shard_repair(vid, bid, [first, second], "get_miss")  # epochs 1, 1
        w.declare()
        sound = c.cm.set_disk_status
        c.cm.set_disk_status = lambda d, status, **k: None if status == DISK_DROPPED else sound(d, status, **k)
        committed = []
        commit = c.worker._commit_unit
        c.worker._commit_unit = lambda prep, src: (commit(prep, src), committed.append(prep["unit"].index),
                                                   c.worker._stop.set() if len(committed) == 1 else None)
        c.scheduler.check_disks()
        c.worker.run_once()  # one unit re-homed, then the worker is stopped
        c.worker._stop.clear()
        assert len(committed) == 1
        healed = committed[0]
        assert c.cm.get_volume(vid).units[healed].epoch == 2
        tasks0 = len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR))
        assert c.scheduler.poll_repair_topic(max_msgs=10_000) >= 1
        assert len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR)) == tasks0
        c.proxy.send_shard_repair(vid, bid, [healed], "inspect")  # against the unit as it stands
        c.scheduler.poll_repair_topic()
        assert len(c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR)) == tasks0 + 1
    finally:
        c.close()


def test_shard_repair_leaves_an_unrouted_unit_to_the_disk_repair(tmp_path):
    """Between a node's death and its declaration its disks are still NORMAL:
    a repair message for its positions is a task that does nothing (it cannot
    write where the unit lives), not one that fails three times."""
    w = Wounded(str(tmp_path), 1)
    try:
        c = w.cluster
        vid, pos = w.held[0]
        bid = next(b.bid for loc, _ in w.objects for b in loc.blobs if b.vid == vid)
        jobs0 = c.codec.stats_snapshot()["jobs"]
        c.proxy.send_shard_repair(vid, bid, [pos], "inspect")
        c.scheduler.poll_repair_topic()
        assert c.worker.wait_idle() == 1
        task = c.scheduler.tasks(sched_mod.KIND_SHARD_REPAIR)[-1]
        assert task.state == sched_mod.TASK_FINISHED and task.retries == 0
        assert c.codec.stats_snapshot()["jobs"] == jobs0
    finally:
        c.close()


def test_re_homed_unit_is_swapped_in_whole(az1):
    loc = az1.access.put(np.random.default_rng(362).bytes(100_000), code_mode=CodeMode.EC12P4)
    vol = az1.cm.get_volume(loc.blobs[0].vid)
    old = vol.units[5]
    was = (old.vuid, old.disk_id, old.node_id, old.epoch)
    dest = az1.worker._dest_for(vol, old.disk_id)
    new = az1.cm.update_volume_unit(vol.vid, 5, dest)
    try:
        assert (old.vuid, old.disk_id, old.node_id, old.epoch) == was, "a reader's old unit must stay whole"
        assert vol.units[5] is new and new is not old
        assert (new.disk_id, new.epoch, new.index) == (dest, was[3] + 1, 5) and new.vuid != was[0]
    finally:
        vol.units[5] = old  # the shards never moved: put the old home back
        az1.cm.disks[dest].chunk_count -= 1
        az1.cm.disks[old.disk_id].chunk_count += 1


def test_the_daemons_tick_does_not_wait_for_a_rebuild(tmp_path):
    """background_tick hands the disk task to the worker's own thread and
    returns; run_background_once is the in-process driver that joins it."""
    w = Wounded(str(tmp_path), 1)
    try:
        c = w.cluster
        chaos.arm("blobnode.put_shard", "delay(0.25)")
        w.declare()
        t0 = time.monotonic()
        stats = c.background_tick()
        took = time.monotonic() - t0
        assert stats["disk_tasks"] == 2 and stats["tasks_ran"] == 0
        assert took < 1.5, f"the tick waited {took:.2f} s for the migrate"
        time.sleep(0.3)
        assert c.scheduler.tasks(sched_mod.KIND_DISK_REPAIR, sched_mod.TASK_WORKING)
        assert c.cm.disk_status(disks_of(c, 1)[0]) == DISK_BROKEN
        chaos.disarm("blobnode.put_shard")
        assert c.worker.wait_idle() >= 0
        deadline = time.monotonic() + 60
        while c.scheduler.tasks(sched_mod.KIND_DISK_REPAIR, sched_mod.TASK_WORKING) and time.monotonic() < deadline:
            c.worker.wait_idle()
        assert all(c.cm.disk_status(d) == DISK_DROPPED for d in disks_of(c, 1))
        w.read_all()
    finally:
        c.close()


# -- the daemon: the operator's call, stop() mid-rebuild, restart ------------------------


def call(addr, method, path):
    host, _, port = addr.rpartition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=30)
    try:
        conn.request(method, path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode() or "null")
    finally:
        conn.close()


def boot(root):
    from chubaofs_tpu import cmd

    d = cmd.start_role({"role": "blobstore", "root": root, "listen": "127.0.0.1:0",
                        "nodes": LAYOUT["n_nodes"], "disksPerNode": LAYOUT["disks_per_node"],
                        "azs": 1, "jaxPlatform": "cpu"})
    return d, d.runner.handles["cluster"]


def test_admin_disk_set_declares_and_refuses(tmp_path):
    d, c = boot(str(tmp_path))
    try:
        disk = disks_of(c, 2)[0]
        assert call(d.addr, "POST", "/admin/disk/set?disk_id=-1&status=broken") == (
            404, {"error": "unknown disk -1"})
        assert call(d.addr, "POST", "/admin/disk/set?disk_id=abc&status=broken")[0] == 400
        for status in ("normal", "dropped", "repaired", ""):
            code, body = call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status={status}")
            assert code == 400 and "cannot be declared" in body["error"]
        assert c.cm.disk_status(disk) == DISK_NORMAL
        assert call(d.addr, "GET", "/admin/disk/set?disk_id=%d&status=broken" % disk)[0] == 404  # POST only
        code, body = call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status=broken")
        assert code == 200 and body["was"] == DISK_NORMAL and body["status"] == DISK_BROKEN and len(body["tasks"]) == 1
        # declared again: nothing changes, no second task
        code, again = call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status=broken")
        assert (code == 200 and again["tasks"] == [] and again["was"] == DISK_BROKEN) or code == 409  # 409: the worker has dropped the empty disk already
        deadline = time.monotonic() + 30  # an empty disk: the worker's thread drops it at once
        while c.cm.disk_status(disk) != DISK_DROPPED and time.monotonic() < deadline:
            time.sleep(0.05)
        assert c.cm.disk_status(disk) == DISK_DROPPED
        code, body = call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status=broken")
        assert code == 409 and "dropped" in body["error"]
    finally:
        d.stop()


def test_stop_mid_rebuild_returns_and_a_restart_finishes_the_task(tmp_path):
    d, c = boot(str(tmp_path))
    objects = []
    try:
        for i in range(3):
            data = np.random.default_rng([36, 9, i]).bytes(5 * MiB)
            objects.append((c.access.put(data).to_json(), data))
        d.runner.call_with("cluster", lambda cl: kill(cl, 1))
        chaos.arm("blobnode.put_shard", "delay(0.25)")  # 12 rows to write: the migrate outlasts the stop
        shards0 = counter("repaired_shards")
        for disk in disks_of(c, 1):
            assert call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status=broken")[0] == 200
        deadline = time.monotonic() + 30
        while counter("repaired_shards") == shards0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert counter("repaired_shards") > shards0, "the rebuild never started"
        assert c.scheduler.tasks(sched_mod.KIND_DISK_REPAIR, sched_mod.TASK_WORKING)
        t0 = time.monotonic()
        d.stop()
        took = time.monotonic() - t0
        assert took < 5.0, f"stop() took {took:.1f} s with a migrate in flight"
    finally:
        chaos.disarm("blobnode.put_shard")
        d.stop()
    d, c = boot(str(tmp_path))
    try:
        lost = disks_of(c, 1)
        assert {c.cm.disk_status(x) for x in lost} <= {DISK_BROKEN, DISK_DROPPED}
        assert DISK_BROKEN in {c.cm.disk_status(x) for x in lost}, "the migrate was cut before its end"
        assert c.scheduler.tasks(sched_mod.KIND_DISK_REPAIR, sched_mod.TASK_PREPARED)
        d.runner.call_with("cluster", lambda cl: kill(cl, 1))  # the node is still gone
        deadline = time.monotonic() + 90
        while any(c.cm.disk_status(x) != DISK_DROPPED for x in lost) and time.monotonic() < deadline:
            time.sleep(0.1)
        assert all(c.cm.disk_status(x) == DISK_DROPPED for x in lost)
        for token, data in objects:
            assert c.access.get(token) == data
        status = {x.disk_id: x.status for x in c.cm.disks.values()}
        placed = {v.vid: [u.disk_id for u in v.units] for v in c.cm.volumes.values()}
        assert reference_rebuild.placement_violations(placed, status) == []
    finally:
        d.stop()
