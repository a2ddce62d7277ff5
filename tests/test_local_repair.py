"""The production 2-AZ LRC deployment healing itself the way its local parities
are bought for: configuration az2-ec16p20l2-localrepair (benchmark/configs),
the benchmark cell az2.get16m-localrepair.

One disk breaks inside an AZ on a node that stays up. From the declaration on
its blobnode answers no shard call for it, readers plan around its units in
one round, and the disk repair rebuilds every global unit it held from that
AZ's OWN local stripe (upstream's recoverByLocalStripe before
recoverByGlobalStripe), reading nothing across the AZ boundary, onto a disk of
the same AZ; the global gather is the fall-back where the AZ's stripe has a
second hole. Held here, on the CPU at small sizes: the worker's row equals the
plain references (benchmark/reference_local_repair.py, reference_rebuild.py,
reference.py) at every global position of both LRC modes, with the job counted
local and no byte counted across the boundary; the fall-back; the refusal; the
read plan; the whole path under readers; and that a non-LRC rebuild is what it
was."""

import threading
import time

import numpy as np
import pytest

from chubaofs_tpu.blobstore import blobnode as blobnode_mod
from chubaofs_tpu.blobstore import scheduler as sched_mod
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.clustermgr import DISK_BROKEN, DISK_DROPPED, DISK_NORMAL
from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
from chubaofs_tpu.utils.exporter import registry

from test_azdown import _json, _load
from test_rebuild import call

MiB = 1 << 20
reference = _load("reference")
reference_rebuild = _load("reference_rebuild")
reference_local = _load("reference_local_repair")
CONFIG = _json("configs", "az2-ec16p20l2-localrepair.json")
TRAFFIC = _json("traffic", "get16m-localrepair.json")
CODE, MODES = CONFIG["code"], CONFIG["modes"]
LAYOUT = dict(n_nodes=CONFIG["layout"]["nodes"], disks_per_node=CONFIG["layout"]["disks_per_node"],
              azs=CONFIG["layout"]["azs"])
GLOBALS = [(m, i) for m in ("EC16P20L2", "EC6P10L2") for i in range(MODES[m]["N"] + MODES[m]["M"])]
SIZE = {"EC16P20L2": 90_000, "EC6P10L2": 40_000}
REBUILD = ("rebuild_decode_jobs", "rebuild_local_jobs", "rebuild_local_fallbacks", "rebuild_cross_az_bytes",
           "repaired_shards", "rebuild_units_committed")


def counter(name, labels=None, role="scheduler"):
    return registry(role).counter(name, labels).value


def rebuild_counters():
    out = {n: counter(n) for n in REBUILD}
    out["read"], out["written"] = (counter("rebuild_bytes", {"kind": k}) for k in ("read", "written"))
    return out


def grown(before):
    return {k: v - before[k] for k, v in rebuild_counters().items()}


def access_counters():
    reg = registry("access")
    out = {p: reg.counter("read_plan_total", {"plan": p}).value for p in ("direct", "one_round", "two_round")}
    out["read_fail"] = sum(reg.counter("read_fail", {"reason": r}).value for r in (
        "no_node", "short", "missing", "timeout", "io", "error", "disk_broken"))
    return out


def refused():
    return counter("io_refused", {"reason": "disk_broken"}, "blobnode")


# -- the configuration, its traffic file and the benchmark's index say one thing ----


def test_configuration_is_az2_with_one_disk_broken_in_each_az():
    base = _json("configs", "az2-ec16p20l2.json")
    for key in ("layout", "policies", "modes", "max_blob_size", "cache_plane", "code", "scale"):
        assert CONFIG[key] == base[key], key
    assert "task_switches_off" not in CONFIG and "switches_off" not in TRAFFIC and "nodes_down" not in TRAFFIC
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["cluster_hosts", "disk_media", "stored_data"]
    cell = next(w for w in bench["workloads"] if w["name"] == "az2.get16m-localrepair")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "get16m-localrepair", 1)
    assert len(cell["why"]) <= 200
    p = TRAFFIC["params"]
    assert TRAFFIC["kind"] == "closed_get_disk_rebuild" and TRAFFIC["loads"] is True
    assert p["declare_broken_disks"] == [{"node": d["node"], "nth": d["nth"]} for d in CONFIG["failure"]["disks"]]
    assert (p["streams"], p["object_bytes"], p["objects"]) == (
        CONFIG["assumed"]["reader_streams"], CONFIG["assumed"]["object_bytes"], CONFIG["assumed"]["objects"])
    t = get_tactic("EC16P20L2")
    _, local_n, local_m = t.local_stripes()[0]
    k = t.shard_size(CONFIG["max_blob_size"])
    assert TRAFFIC["warm"]["decode_shapes"] == [
        {"n": local_n, "m": local_m, "rows": 1, "shard_bytes": k,
         "max_count": sched_mod.RepairWorker.DECODE_AHEAD + 1}]
    assert TRAFFIC["load_disk_bytes"] == p["objects"] * p["object_bytes"] * t.total / t.N
    need = TRAFFIC["verify"]["counter_delta_min"]
    assert need["cfs_scheduler_rebuild_local_jobs"] == need["cfs_scheduler_repaired_shards"] == 300
    assert need["cfs_scheduler_rebuild_units_committed"] == 1
    reported = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
                if "workloads" not in m or cell["name"] in m["workloads"]}
    lrc = {m["name"] for m in bench["per_layer"] if m["name"].startswith("lrc_")}
    # and, since PR 46, the one shared metric that lists this cell: the codec pool's reuse share
    assert len(lrc) == 19 and lrc | {"get_MBps", "setup_s", "get_codec_buffer_reuse_share"} == reported
    assert all(m["workloads"] == [cell["name"]] and m["moves"] == "get_MBps"
               for m in bench["per_layer"] if m["name"] in lrc)


def test_the_first_two_volumes_place_the_four_positions_the_file_states(tmp_path):
    c = MiniCluster(str(tmp_path), **LAYOUT)
    try:
        c.access.put(np.random.default_rng(42).bytes(5 * MiB))  # two blobs: both active volumes
        held = {f"volume {v.vid}": {f"disk {u.disk_id}": u.index for u in v.units if u.disk_id in (1000, 2000)}
                for v in c.cm.volumes.values()}
        assert held == CONFIG["failure"]["stripe_positions"]
        disks = [d.disk_id for d in c.cm.disks.values()]
        for d in CONFIG["failure"]["disks"]:
            mine = [x for x in disks if c.cm.disks[x].node_id == d["node"]]
            assert mine[d["nth"]] == d["disk_id"] and c.cm.disks[d["disk_id"]].az == d["az"]
    finally:
        c.close()


# -- the worker's row by the local stripe, at every global position of both LRC modes ----


@pytest.fixture(scope="module")
def az2(tmp_path_factory):
    c = MiniCluster(str(tmp_path_factory.mktemp("az2")), **LAYOUT)
    yield c
    c.close()


def put_one(c, mode, seed):
    data = np.random.default_rng([42, seed]).bytes(SIZE[mode] + seed)
    loc = c.access.put(data, code_mode=CodeMode[mode])
    assert len(loc.blobs) == 1
    blob = loc.blobs[0]
    blob.loc = loc  # for a GET of it
    return data, blob, c.cm.get_volume(blob.vid)


def rebuilt_by_the_worker(c, vol, unit, bid):
    w, t = c.worker, vol.tactic()
    gathered = w._gather_for_unit(vol, t, unit, bid)
    row = w._submit_row(vol, t, unit, bid, gathered)
    return gathered[0], row if isinstance(row, bytes) else row.result()


@pytest.mark.parametrize("mode,index", GLOBALS, ids=[f"{m}-{i}" for m, i in GLOBALS])
def test_row_by_the_local_stripe_equals_every_reference(az2, mode, index):
    """worker == reference_local_repair (the AZ's stripe alone) ==
    reference_rebuild (N global survivors) == the encoded row; the job is
    counted local, local_n survivors were read and none across the boundary."""
    data, blob, vol = put_one(az2, mode, index)
    unit = vol.units[index]
    az2.nodes[unit.node_id].lose_shard(unit.vuid, blob.bid)
    before = rebuild_counters()
    kind, row = rebuilt_by_the_worker(az2, vol, unit, blob.bid)
    m = MODES[mode]
    stripe = reference.encode(data, m, CODE)
    left = [None if p == index else s.tobytes() for p, s in enumerate(stripe)]
    az = reference_local.az_of(m, index)
    own_az = [s if reference_local.az_of(m, p) == az else None for p, s in enumerate(left)]
    assert kind == "local_rows"
    assert row == reference_local.local_rebuilt_row(own_az, index, m, CODE), "worker != the local reference"
    assert row == reference_rebuild.rebuilt_row(left, index, m, CODE), "worker != the global reference"
    assert row == stripe[index].tobytes(), "rebuilt row != the reference stripe's"
    local_n, _ = reference_local.geometry(m)
    got = grown(before)
    assert (got["rebuild_decode_jobs"], got["rebuild_local_jobs"], got["rebuild_local_fallbacks"]) == (1, 1, 0)
    assert got["read"] == local_n * len(row) and got["rebuild_cross_az_bytes"] == 0


def test_the_local_reference_refuses_what_it_cannot_solve_and_counts_the_boundary():
    m = MODES["EC16P20L2"]
    stripe = [s.tobytes() for s in reference.encode(b"y" * 70_000, m, CODE)]
    assert reference_local.local_stripe(m, 0) == list(range(8)) + list(range(16, 26)) + [36]
    assert reference_local.local_stripe(m, 1) == list(range(8, 16)) + list(range(26, 36)) + [37]
    two_holes = [None if p in (0, 3) else s for p, s in enumerate(stripe)]
    with pytest.raises(ValueError):
        reference_local.local_rebuilt_row(two_holes, 0, m, CODE)
    with pytest.raises(ValueError):
        reference_local.local_rebuilt_row(stripe, 36, m, CODE)  # a local parity is not a global position
    with pytest.raises(ValueError):
        reference_local.local_rebuilt_row(stripe, 0, {**m, "L": 0}, CODE)
    # several stripes end to end are solved at once (the drive's blocks)
    other = [s.tobytes() for s in reference.encode(b"z" * 70_000, m, CODE)]
    both = [None if p == 9 else a + b for p, (a, b) in enumerate(zip(stripe, other))]
    assert reference_local.local_rebuilt_row(both, 9, m, CODE) == stripe[9] + other[9]
    assert reference_local.cross_az_reads(m, 0, reference_local.local_stripe(m, 0)) == 0
    assert reference_local.cross_az_reads(m, 0, range(1, 17)) == 8  # a global gather: N survivors in stripe order
    assert reference_local.cross_az_reads(m, 30, [8, 9, 37, 0, 16, 36]) == 3


@pytest.mark.parametrize("hole", ["broken_disk", "unreadable_shard"])
@pytest.mark.parametrize("mode", ["EC16P20L2", "EC6P10L2"])
def test_a_second_hole_in_the_az_stripe_takes_the_global_gather(az2, mode, hole):
    """local_m = 1 heals one hole an AZ: with a second unit of the same AZ's
    stripe on a disk that is not NORMAL (known without a read) or unreadable
    (found by the read), the stripe is gathered from N survivors of any AZ,
    counted as a fall-back, and still equals the references."""
    data, blob, vol = put_one(az2, mode, 77 + len(hole))
    m, t = MODES[mode], vol.tactic()
    lost, second = 1, reference_local.local_stripe(m, 0)[-2]  # a data unit and a global parity of AZ 0
    assert reference_local.az_of(m, lost) == reference_local.az_of(m, second) == 0
    unit = vol.units[lost]
    az2.nodes[unit.node_id].lose_shard(unit.vuid, blob.bid)
    u2 = vol.units[second]
    if hole == "broken_disk":
        az2.cm.set_disk_status(u2.disk_id, DISK_BROKEN, reason="test")
    else:
        az2.nodes[u2.node_id].lose_shard(u2.vuid, blob.bid)
    try:
        before = rebuild_counters()
        kind, row = rebuilt_by_the_worker(az2, vol, unit, blob.bid)
    finally:
        az2.cm.set_disk_status(u2.disk_id, DISK_NORMAL, reason="test")
    stripe = reference.encode(data, m, CODE)
    left = [None if p in (lost, second) else s.tobytes() for p, s in enumerate(stripe)]
    assert kind == "rows"
    assert row == reference_rebuild.rebuilt_row(left, lost, m, CODE) == stripe[lost].tobytes()
    with pytest.raises(ValueError):
        reference_local.local_rebuilt_row(left, lost, m, CODE)
    got = grown(before)
    assert (got["rebuild_decode_jobs"], got["rebuild_local_jobs"], got["rebuild_local_fallbacks"]) == (1, 0, 1)
    # N survivors in stripe order: those of AZ 1 are read across the boundary
    present = [p for p in range(t.N + t.M) if p not in (lost, second)][: t.N]
    local_reads = 0 if hole == "broken_disk" else reference_local.geometry(m)[0] - 1
    assert got["rebuild_cross_az_bytes"] == reference_local.cross_az_reads(m, lost, present) * len(row) > 0
    assert got["read"] == (t.N + local_reads) * len(row)


# -- a BROKEN disk answers nothing; its node's other disks serve -----------------------------


def test_a_broken_disk_refuses_get_put_list_and_serves_again_when_normal(az2):
    data, blob, vol = put_one(az2, "EC16P20L2", 501)
    unit = vol.units[2]
    node = az2.nodes[unit.node_id]
    sibling = next(u for u in vol.units if u.node_id == unit.node_id and u.disk_id != unit.disk_id)
    shard = node.get_shard(unit.vuid, blob.bid)
    r0 = refused()
    az2.cm.set_disk_status(unit.disk_id, DISK_BROKEN, reason="operator")
    try:
        for op in (lambda: node.get_shard(unit.vuid, blob.bid),
                   lambda: node.put_shard(unit.vuid, blob.bid, shard),
                   lambda: node.list_shards(unit.vuid),
                   lambda: node.get_shard_combined(unit.vuid, blob.bid, b"\x01"),
                   lambda: node.delete_shard(unit.vuid, blob.bid)):
            with pytest.raises(blobnode_mod.DiskBroken) as e:
                op()
            assert blobnode_mod.classify_io_error(e.value) == "disk_broken"
        assert refused() - r0 == 5
        assert node.get_shard(sibling.vuid, blob.bid) and node.list_shards(sibling.vuid)
        assert refused() - r0 == 5
    finally:
        az2.cm.set_disk_status(unit.disk_id, DISK_NORMAL, reason="test")
    assert node.get_shard(unit.vuid, blob.bid) == shard
    assert [s.bid for s in node.list_shards(unit.vuid)].count(blob.bid) == 1


def test_a_disk_the_node_itself_reports_broken_is_refused_from_that_beat_on(az2):
    data, blob, vol = put_one(az2, "EC6P10L2", 502)
    unit = vol.units[4]
    node = az2.nodes[unit.node_id]
    node._io_errors[unit.disk_id] = 3
    try:
        node.heartbeat(az2.cm)
        assert az2.cm.disk_status(unit.disk_id) == DISK_BROKEN
        with pytest.raises(blobnode_mod.DiskBroken):
            node.get_shard(unit.vuid, blob.bid)
    finally:
        node._io_errors[unit.disk_id] = 0
        az2.cm.set_disk_status(unit.disk_id, DISK_NORMAL, reason="test")
    assert node.get_shard(unit.vuid, blob.bid)


# -- the read plan goes by the disk's status ---------------------------------------------------


@pytest.mark.parametrize("index,plan", [(0, "one_round"), (9, "one_round"), (20, "direct")],
                         ids=["data-unit-of-az0", "data-unit-of-az1", "parity-unit"])
def test_get_plans_around_a_broken_disk_without_a_read_to_it(az2, index, plan):
    """A blob with a DATA unit on a BROKEN disk is one_round: no read is
    issued to the disk (its blobnode refuses nothing), no read_fail is counted,
    no two_round; a broken PARITY unit leaves the blob direct."""
    data, blob, vol = put_one(az2, "EC16P20L2", 600 + index)
    unit = vol.units[index]
    az2.cm.set_disk_status(unit.disk_id, DISK_BROKEN, reason="operator")
    try:
        before, r0 = access_counters(), refused()
        decoded0 = counter("read_bytes", {"kind": "decoded"}, "access")
        assert az2.access.get(blob.loc) == data
        time.sleep(0.2)  # the probe of unread shards runs off the GET's path
        after = access_counters()
        assert {k: after[k] - before[k] for k in after} == {
            "direct": int(plan == "direct"), "one_round": int(plan == "one_round"), "two_round": 0, "read_fail": 0}
        assert refused() == r0, "a read reached the broken disk"
        assert (counter("read_bytes", {"kind": "decoded"}, "access") > decoded0) == (plan == "one_round")
    finally:
        az2.cm.set_disk_status(unit.disk_id, DISK_NORMAL, reason="test")
    assert az2.access.get(blob.loc) == data


# -- one disk broken in each AZ, declared, rebuilt under reads: the whole path ----------------------


class TwoDisks:
    """The 12 x 4 two-AZ cluster with 5 MiB objects (two blobs each, 4 MiB and
    1 MiB, both EC16P20L2, one in each active volume), the same data in the
    store model, and the configuration's two disks."""

    def __init__(self, root):
        self.cluster = c = MiniCluster(root, **LAYOUT)
        self.disks = [d["disk_id"] for d in CONFIG["failure"]["disks"]]
        self.objects = []
        for i in range(3):
            data = np.random.default_rng([42, 5, i]).bytes(5 * MiB)
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
        self.held = [(v.vid, u.index) for v in c.cm.volumes.values() for u in v.units if u.disk_id in self.disks]
        self.az = {(v.vid, u.index): c.cm.disks[u.disk_id].az
                   for v in c.cm.volumes.values() for u in v.units if u.disk_id in self.disks}

    def declare(self):
        for d in self.disks:
            self.cluster.cm.set_disk_status(d, DISK_BROKEN, reason="operator")
            # "lose one disk" in the dict model: BROKEN, and what it held is gone
            self.model.status[d] = reference_rebuild.BROKEN
        for key in [k for k in self.model.shards if self.model.volumes[k[0]][1][k[1]] in self.disks]:
            del self.model.shards[key]

    def read_all(self):
        for loc, data in self.objects:
            assert self.cluster.access.get(loc) == data


@pytest.fixture
def two_disks(tmp_path):
    w = TwoDisks(str(tmp_path))
    yield w
    w.cluster.close()


def test_one_disk_in_each_az_heals_locally_under_reads(two_disks):
    """Readers in threads before, while and after; every unit rebuilt from its
    own AZ's local stripe (every job local, 18 reads a shard, none across the
    boundary, no fall-back), re-homed on a NORMAL disk of the SAME AZ that
    holds no other unit of its volume, each disk DROPPED only after its last
    commit; every rebuilt shard equals the model's and the local reference's
    row; no GET took two rounds or counted a failed read."""
    w, c, worker = two_disks, two_disks.cluster, two_disks.cluster.worker
    assert sorted(w.held) == [(1, 0), (1, 8), (2, 5), (2, 13)]
    w.read_all()
    before_access, r0 = access_counters(), refused()
    w.declare()
    w.read_all()  # degraded by plan, nothing rebuilt yet: no tick has run
    mid = access_counters()
    assert mid["one_round"] - before_access["one_round"] == 6
    assert (mid["two_round"], mid["read_fail"], mid["direct"]) == (
        before_access["two_round"], before_access["read_fail"], before_access["direct"])
    assert refused() == r0, "a GET's read reached a broken disk"
    sound, commits = worker._commit_unit, []

    def commit(prep, source_disk_id):
        assert c.cm.disk_status(source_disk_id) == DISK_BROKEN
        sound(prep, source_disk_id)
        commits.append((prep["vol"].vid, prep["unit"].index))
        left = [k for k in w.held if k not in commits and c.cm.get_volume(k[0]).units[k[1]].disk_id == source_disk_id]
        assert c.cm.disk_status(source_disk_id) == DISK_BROKEN or not left

    worker._commit_unit = commit
    stop, errors = threading.Event(), []

    def reader():
        try:
            while not stop.is_set():
                w.read_all()
        except Exception as e:  # an assert in a thread is the test's failure
            errors.append(e)

    threads = [threading.Thread(target=reader, name=f"reader{i}") for i in range(2)]
    before = rebuild_counters()
    for th in threads:
        th.start()
    try:
        stats = c.run_background_once()
    finally:
        stop.set()
        for th in threads:
            th.join()
    assert not errors, errors
    assert stats["disk_tasks"] == 2 and sorted(commits) == sorted(w.held)
    assert all(c.cm.disk_status(d) == DISK_DROPPED for d in w.disks)
    w.read_all()
    after = access_counters()
    assert (after["two_round"], after["read_fail"]) == (before_access["two_round"], before_access["read_fail"])
    got = grown(before)
    shards = 3 * len(w.held) // 2  # three blobs a volume, two units of each volume
    assert got["repaired_shards"] == got["rebuild_decode_jobs"] == got["rebuild_local_jobs"] == shards * 2
    assert (got["rebuild_local_fallbacks"], got["rebuild_cross_az_bytes"]) == (0, 0)
    assert got["read"] == 18 * got["written"] and got["rebuild_units_committed"] == 4
    # every rebuilt shard against the store model's rebuild and the local reference
    assert w.model.rebuild() == shards * 2
    for vid, pos in w.held:
        unit = c.cm.get_volume(vid).units[pos]
        assert unit.epoch == 2 and c.cm.disks[unit.disk_id].az == w.az[(vid, pos)]
        m = MODES[CodeMode(c.cm.get_volume(vid).code_mode).name]
        for (v, p, bid), want in w.model.shards.items():
            if (v, p) == (vid, pos):
                assert c.nodes[unit.node_id].get_shard(unit.vuid, bid) == want, (vid, pos, bid)
                stripe = [None if q == pos else w.model.shards.get((vid, q, bid)) for q in range(38)]
                assert reference_local.local_rebuilt_row(stripe, pos, m, CODE) == want
    status = {d.disk_id: d.status for d in c.cm.disks.values()}
    placed = {v.vid: [u.disk_id for u in v.units] for v in c.cm.volumes.values()}
    assert reference_rebuild.placement_violations(placed, status) == []
    assert w.model.violations() == []


def test_the_rebuild_reads_nothing_from_the_broken_disks(two_disks):
    """Nothing is copied from a broken disk: with every shard call of the two
    disks' chunks counted, the rebuild and the readers beside it make none
    that is served, and the rows still equal the model's."""
    w, c = two_disks, two_disks.cluster
    served = []
    for d in w.disks:
        node = c.nodes[c.cm.disks[d].node_id]
        for cid, chunk in node.disks[d].chunks.items():
            for name in ("get", "put"):
                sound = getattr(chunk, name)
                setattr(chunk, name, lambda *a, _s=sound, _c=cid, _n=name, **k: (served.append((_c, _n)), _s(*a, **k))[1])
    w.declare()
    c.run_background_once()
    w.read_all()
    assert served == [] and all(c.cm.disk_status(d) == DISK_DROPPED for d in w.disks)


# -- a non-LRC rebuild is what it was --------------------------------------------------------


def test_a_non_lrc_rebuild_still_gathers_n_survivors(tmp_path):
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        data = np.random.default_rng(4242).bytes(300_000)
        loc = c.access.put(data, code_mode=CodeMode.EC12P4)
        blob, vol = loc.blobs[0], c.cm.get_volume(loc.blobs[0].vid)
        unit = vol.units[3]
        c.nodes[unit.node_id].lose_shard(unit.vuid, blob.bid)
        before = rebuild_counters()
        kind, row = rebuilt_by_the_worker(c, vol, unit, blob.bid)
        az1 = _json("configs", "az1-ec12p4-rebuild.json")
        assert kind == "rows" and row == reference.encode(data, az1["modes"]["EC12P4"], az1["code"])[3].tobytes()
        got = grown(before)
        assert (got["rebuild_decode_jobs"], got["rebuild_local_jobs"], got["rebuild_local_fallbacks"]) == (1, 0, 0)
        assert got["read"] == 12 * len(row) and got["rebuild_cross_az_bytes"] == 0
    finally:
        c.close()


# -- the daemon: the declaration with the switch held, the release, the series ------------------


def test_daemon_declares_with_the_switch_held_and_rebuilds_at_its_release(tmp_path):
    """What the cell's generator does over HTTP: the series render at 0 from
    the boot; a disk declared with disk_repair held is refused I/O at once and
    gets no task; the release of the switch makes the task and wakes the
    worker, with no tick to wait for."""
    from chubaofs_tpu import cmd

    d = cmd.start_role({"role": "blobstore", "root": str(tmp_path), "listen": "127.0.0.1:0",
                        "nodes": LAYOUT["n_nodes"], "disksPerNode": LAYOUT["disks_per_node"],
                        "azs": LAYOUT["azs"], "jaxPlatform": "cpu"})
    try:
        c = d.runner.handles["cluster"]
        import http.client

        host, _, port = d.addr.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        for name in ("rebuild_local_jobs", "rebuild_cross_az_bytes", "rebuild_local_fallbacks"):
            assert f"\ncfs_scheduler_{name} " in text, name
        data = np.random.default_rng(4243).bytes(5 * MiB)
        token = c.access.put(data).to_json()
        assert call(d.addr, "POST", "/admin/switch?name=disk_repair&enabled=0") == (200, {"disk_repair": False})
        disk = [x["disk_id"] for x in call(d.addr, "GET", "/admin/disks")[1] if x["node_id"] == 1][0]
        code, body = call(d.addr, "POST", f"/admin/disk/set?disk_id={disk}&status=broken")
        assert code == 200 and body["status"] == DISK_BROKEN and body["tasks"] == []
        unit = next(u for u in c.cm.get_volume(1).units if u.disk_id == disk)
        with pytest.raises(blobnode_mod.DiskBroken):
            c.nodes[1].list_shards(unit.vuid)
        assert c.access.get(token) == data
        local0 = counter("rebuild_local_jobs")
        assert call(d.addr, "POST", "/admin/switch?name=disk_repair&enabled=1") == (200, {"disk_repair": True})
        deadline = time.monotonic() + 60
        while c.cm.disk_status(disk) != DISK_DROPPED and time.monotonic() < deadline:
            time.sleep(0.05)
        assert c.cm.disk_status(disk) == DISK_DROPPED
        assert counter("rebuild_local_jobs") - local0 == 2 and c.access.get(token) == data
    finally:
        d.stop()
