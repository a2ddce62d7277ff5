"""LRC + multi-AZ on the live blobstore path.

Reference semantics under test:
  * dark-AZ PUT quorum — tolerate exactly one fully-failed AZ at >=3 AZs iff
    every other AZ is fully written (stream_put.go:405-437);
  * quorum counts only global-stripe shards (stream_put.go:226 maxWrittenIndex);
  * LRC local-stripe-first repair reading ONLY same-AZ shards
    (work_shard_recover.go:517 recoverByLocalStripe);
  * AZ-aware code-mode policy puts LRC modes on the live PUT path.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from chubaofs_tpu.blobstore.access import (
    QuorumError,
    default_policies,
    select_code_mode,
)
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.clustermgr import parse_vuid
from chubaofs_tpu.codec.codemode import CodeMode, get_tactic

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def _reference():
    """benchmark/reference.py, loaded by path: the plain two-stage codec that
    imports nothing of the program."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference", os.path.join(BENCH, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _deployment(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# the LRC deployments of the benchmark at test size: mode, its configuration
# file, an object size the file's policy table sends to that mode, the cluster.
# az2 is the file's own layout (24 disks an AZ for EC16P20L2's 19 units); az3
# is cut to 6 x 2 (EC6P3L3 places 4 units an AZ on 4 disks).
AZ3 = dict(n_nodes=6, disks_per_node=2, azs=3)
AZ2 = dict(n_nodes=12, disks_per_node=4, azs=2)
LRC = {
    "ec6p3l3": (CodeMode.EC6P3L3, "az3-ec6p3l3", 2_000_000, AZ3),
    "ec16p20l2": (CodeMode.EC16P20L2, "az2-ec16p20l2", 1_100_000, AZ2),
    "ec6p10l2": (CodeMode.EC6P10L2, "az2-ec16p20l2", 500_000, AZ2),
}


class DownNode:
    """A blobnode whose every RPC fails (a fully-dark host)."""

    def __getattr__(self, name):
        def _fail(*a, **k):
            raise RuntimeError("node down")

        return _fail


class RecordingNode:
    """Pass-through blobnode that records which shards were read."""

    def __init__(self, inner):
        self._inner = inner
        self.reads = []

    def get_shard(self, vuid, bid, offset=0, size=None):
        self.reads.append((vuid, bid))
        return self._inner.get_shard(vuid, bid, offset=offset, size=size)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class DroppingNode:
    """Pass-through blobnode that refuses the shard writes of some stripe
    positions (the unit's disk answers, the write fails)."""

    def __init__(self, inner, drop_idx):
        self._inner = inner
        self._drop = set(drop_idx)

    def put_shard(self, vuid, bid, payload):
        if parse_vuid(vuid)[1] in self._drop:
            raise RuntimeError("shard write dropped")
        return self._inner.put_shard(vuid, bid, payload)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture
def cluster3az(tmp_path):
    # 3 AZs x 2 nodes x 2 disks: EC6P3L3 places 4 units per AZ on 4 disks
    c = MiniCluster(str(tmp_path), **AZ3)
    yield c
    c.close()


@pytest.fixture(params=list(LRC))
def lrc(request, tmp_path):
    """(cluster, mode, configuration, object size) of one LRC deployment."""
    mode, config_name, size, layout = LRC[request.param]
    config = _deployment(config_name)
    assert layout["azs"] == config["layout"]["azs"]
    c = MiniCluster(str(tmp_path), **layout)
    real = dict(c.nodes)
    yield c, mode, config, size
    c.nodes.clear()  # tests swap in Down / Dropping / Recording nodes
    c.nodes.update(real)
    c.close()


def _az_nodes(cluster, az):
    """node_ids whose disks live in the given AZ."""
    return sorted({d.node_id for d in cluster.cm.disks.values() if d.az == az})


def blob_bytes(rng, n):
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


def test_default_policies_put_lrc_on_live_path():
    """Multi-AZ clusters select LRC modes for archive-sized puts."""
    p3 = default_policies(3)
    assert select_code_mode(2_000_000, p3) == CodeMode.EC6P3L3
    assert get_tactic(select_code_mode(2_000_000, p3)).L > 0
    p2 = default_policies(2)
    assert select_code_mode(2_000_000, p2) == CodeMode.EC16P20L2
    assert select_code_mode(1000, p2) == CodeMode.EC6P10L2
    # single-AZ keeps the plain-RS ladder
    assert select_code_mode(2_000_000, default_policies(1)) == CodeMode.EC12P4


def _stored_stripe(c, blob):
    """Every stripe position's stored bytes, read from the blobnodes."""
    return [c.nodes[u.node_id].get_shard(u.vuid, blob.bid)
            for u in c.cm.get_volume(blob.vid).units]


def test_access_selects_lrc_from_cluster_topology(lrc, rng):
    """An Access built on a multi-AZ cluster routes a put of the policy
    table's size through the LRC mode, and every shard it stores (data,
    global parity, local parity) is the plain reference's: the program's ONE
    composed matmul (lrc_parity_matrix) against the reference's two stages."""
    c, mode, config, size = lrc
    data = blob_bytes(rng, size)
    loc = c.access.put(data)
    assert loc.code_mode == int(mode)
    assert c.access.get(loc) == data
    t = get_tactic(mode)
    geometry = config["modes"][mode.name]
    assert geometry == {"N": t.N, "M": t.M, "L": t.L, "az_count": t.az_count,
                        "put_quorum": t.put_quorum}
    (blob,) = loc.blobs
    stored = _stored_stripe(c, blob)
    assert len(stored) == t.total
    want = _reference().encode(data, geometry, config["code"])
    assert want.shape == (t.total, t.shard_size(size))
    for idx, shard in enumerate(stored):
        assert shard == want[idx].tobytes(), f"{mode.name} shard {idx} differs"


def test_units_land_in_their_az_on_distinct_disks(lrc):
    """createvolume.go: total / az_count units in each AZ (19 for EC16P20L2),
    the AZ's data + global parity + local parity, no two on one disk."""
    c, mode, _, _ = lrc
    t = get_tactic(mode)
    vol = c.cm.alloc_volume(int(mode))
    assert len({u.disk_id for u in vol.units}) == t.total
    for az in range(t.az_count):
        disks = [c.cm.disks[vol.units[i].disk_id] for i in t.shards_in_az(az)]
        assert len(disks) == t.total // t.az_count
        assert {d.az for d in disks} == {az}


@pytest.mark.parametrize("down", ["node", "az"])
def test_degraded_get_is_byte_equal(lrc, rng, down):
    """GET with the node of data shard 0 down, and with the whole of AZ 0
    down (EC16P20L2: 19 of 38 shards gone, 8 data + 10 global parities left)."""
    c, mode, _, size = lrc
    data = blob_bytes(rng, size)
    loc = c.access.put(data)
    assert loc.code_mode == int(mode)
    vol = c.cm.get_volume(loc.blobs[0].vid)
    dark = _az_nodes(c, 0) if down == "az" else [vol.units[0].node_id]
    t = get_tactic(mode)
    alive = [u.index for u in vol.units
             if u.index < t.global_count and u.node_id not in dark]
    assert t.N <= len(alive) < t.global_count
    for n in dark:
        c.nodes[n] = DownNode()
    assert c.access.get(loc) == data


@pytest.mark.parametrize("globals_written", ["short", "quorum"])
def test_put_quorum_counts_global_shards_only(lrc, rng, globals_written):
    """stream_put.go:226 maxWrittenIndex = N + M: with every local parity
    written, put_quorum - 1 global shards are refused (EC16P20L2: 33 + 2
    locals = 35 shards on disk, still short) and put_quorum are accepted."""
    c, mode, _, size = lrc
    t = get_tactic(mode)
    keep = t.put_quorum - (globals_written == "short")
    drop = range(keep, t.global_count)  # the highest global parities
    for n, node in list(c.nodes.items()):
        c.nodes[n] = DroppingNode(node, drop)
    data = blob_bytes(rng, size)
    if globals_written == "short":
        with pytest.raises(QuorumError, match=f"wrote {keep}/{t.global_count}"):
            c.access.put(data)
        return
    loc = c.access.put(data)
    stored = 0
    for u in c.cm.get_volume(loc.blobs[0].vid).units:
        try:
            stored += bool(c.nodes[u.node_id].get_shard(u.vuid, loc.blobs[0].bid))
        except Exception:
            assert u.index in drop
    assert stored == t.put_quorum + t.L
    assert c.access.get(loc) == data


@pytest.mark.parametrize("lrc", ["ec16p20l2", "ec6p10l2"], indirect=True)
def test_dark_az_put_is_refused_under_three_azs(lrc, rng):
    """The dark-AZ tolerance needs >= 3 AZs (stream_put.go:405-437; the 3-AZ
    side is test_dark_az_put_get_heal): a 2-AZ mode with a whole AZ down has
    half its globals and fails the quorum."""
    c, mode, _, size = lrc
    assert get_tactic(mode).az_count == 2
    for n in _az_nodes(c, 1):
        c.nodes[n] = DownNode()
    with pytest.raises(QuorumError):
        c.access.put(blob_bytes(rng, size))


def test_dark_az_put_get_heal(cluster3az, rng):
    """PUT with one whole AZ down succeeds; GET reconstructs; repair heals.

    The signature LRC/multi-AZ flow: stream_put.go:405-437 tolerance, then the
    failed shards ride the repair topic back to full redundancy."""
    c = cluster3az
    dark_az = 2
    down = _az_nodes(c, dark_az)
    saved = {n: c.nodes[n] for n in down}
    for n in down:
        c.nodes[n] = DownNode()

    data = blob_bytes(rng, 2_000_000)
    loc = c.access.put(data, code_mode=CodeMode.EC6P3L3)

    # degraded GET with the AZ still dark
    assert c.access.get(loc) == data

    # exactly the dark AZ's shards were queued for repair
    t = get_tactic(CodeMode.EC6P3L3)
    vol = c.cm.get_volume(loc.blobs[0].vid)
    bid = loc.blobs[0].bid
    dark_idx = set(t.shards_in_az(dark_az))
    msgs = c.proxy.topics["shard_repair"].consume("peek", 100)
    assert msgs and set(msgs[0]["bad_idx"]) == dark_idx

    # lights back on: background repair heals every missing shard
    for n, node in saved.items():
        c.nodes[n] = node
    c.run_background_once()
    for idx in sorted(dark_idx):
        unit = vol.units[idx]
        got = c.nodes[unit.node_id].get_shard(unit.vuid, bid)
        assert len(got) == t.shard_size(loc.blobs[0].size)
    # the healed object reads back clean via the fast path
    assert c.access.get(loc) == data


def test_two_dark_azs_fail_put(cluster3az, rng):
    """Two dark AZs break both the quorum and the tolerance rule."""
    c = cluster3az
    saved = dict(c.nodes)
    for az in (1, 2):
        for n in _az_nodes(c, az):
            c.nodes[n] = DownNode()
    try:
        with pytest.raises(QuorumError):
            c.access.put(blob_bytes(rng, 2_000_000), code_mode=CodeMode.EC6P3L3)
    finally:
        c.nodes.update(saved)


def test_local_parity_does_not_satisfy_quorum(tmp_path, rng):
    """Quorum counts global shards only (maxWrittenIndex = N+M): killing all
    but one AZ's globals fails the put even if locals landed."""
    c = MiniCluster(str(tmp_path), n_nodes=6, disks_per_node=2, azs=3)
    try:
        t = get_tactic(CodeMode.EC6P3L3)
        # darken two AZs partially: one global shard down in each of az1, az2
        # leaves written globals = 7 < put_quorum 9 and no single-dark-AZ out
        vol = c.cm.alloc_volume(int(CodeMode.EC6P3L3))
        down_nodes = set()
        for az in (1, 2):
            g = [i for i in t.shards_in_az(az) if i < t.global_count][0]
            down_nodes.add(vol.units[g].node_id)
        saved = dict(c.nodes)
        for n in down_nodes:
            c.nodes[n] = DownNode()
        try:
            with pytest.raises(QuorumError):
                c.access.put(blob_bytes(rng, 2_000_000), code_mode=CodeMode.EC6P3L3)
        finally:
            c.nodes.update(saved)
    finally:
        c.close()


def _repair_recording_reads(c, vol, bid, lost_idx):
    """Lose one shard, queue its repair, run one background pass with the
    volume inspector off (it legitimately sweeps every AZ; only the REPAIR's
    read set is asserted on) -> node ids the repair read from."""
    from chubaofs_tpu.blobstore.taskswitch import SWITCH_VOL_INSPECT

    unit = vol.units[lost_idx]
    c.nodes[unit.node_id].lose_shard(unit.vuid, bid)
    c.proxy.send_shard_repair(vol.vid, bid, [lost_idx], "test")
    c.scheduler.switches.set(SWITCH_VOL_INSPECT, False)
    recorders = {n: RecordingNode(node) for n, node in c.nodes.items()}
    c.nodes.clear()
    c.nodes.update(recorders)
    c.run_background_once()
    return {n for n, r in recorders.items() if r.reads}


def test_local_stripe_repair_reads_same_az_only(lrc, rng):
    """Losing one data shard inside an AZ repairs from that AZ's local stripe
    alone (work_shard_recover.go:517): EC16P20L2 reads AZ 0's 7 data + 10
    global parities + 1 local, never the other AZ."""
    c, mode, _, size = lrc
    data = blob_bytes(rng, size)
    loc = c.access.put(data, code_mode=mode)
    t = get_tactic(mode)
    vol = c.cm.get_volume(loc.blobs[0].vid)
    bid = loc.blobs[0].bid

    lost_idx = t.shards_in_az(0)[0]  # a data shard in AZ 0
    unit = vol.units[lost_idx]
    before = c.nodes[unit.node_id].get_shard(unit.vuid, bid)
    read_nodes = _repair_recording_reads(c, vol, bid, lost_idx)

    assert read_nodes, "repair must have read something"
    assert read_nodes <= set(_az_nodes(c, 0)), f"repair read outside AZ 0: {read_nodes}"
    assert c.nodes[unit.node_id].get_shard(unit.vuid, bid) == before
    assert c.access.get(loc) == data


def test_lost_local_parity_recomputed_in_az(lrc, rng):
    """A lost local parity is regenerated from its AZ's global shards."""
    c, mode, _, size = lrc
    data = blob_bytes(rng, size)
    loc = c.access.put(data, code_mode=mode)
    t = get_tactic(mode)
    vol = c.cm.get_volume(loc.blobs[0].vid)
    bid = loc.blobs[0].bid

    local_idx = t.shards_in_az(1)[-1]  # AZ 1's local parity
    assert local_idx >= t.global_count
    unit = vol.units[local_idx]
    before = c.nodes[unit.node_id].get_shard(unit.vuid, bid)
    read_nodes = _repair_recording_reads(c, vol, bid, local_idx)

    assert read_nodes <= set(_az_nodes(c, 1)), f"repair read outside AZ 1: {read_nodes}"
    assert c.nodes[unit.node_id].get_shard(unit.vuid, bid) == before


def test_two_az_lrc_roundtrip(tmp_path, rng):
    """EC6P10L2 (2-AZ LRC) full put/get/degraded-get on a 2-AZ cluster."""
    # EC6P10L2 places 9 units per AZ: 3 nodes x 3 disks each side
    c = MiniCluster(str(tmp_path), n_nodes=6, disks_per_node=3, azs=2)
    try:
        data = blob_bytes(rng, 500_000)
        loc = c.access.put(data)
        assert loc.code_mode == int(CodeMode.EC6P10L2)
        assert c.access.get(loc) == data
        # kill two data shards; direct GET degrades but still serves
        vol = c.cm.get_volume(loc.blobs[0].vid)
        for idx in (0, 1):
            u = vol.units[idx]
            c.nodes[u.node_id].lose_shard(u.vuid, loc.blobs[0].bid)
        assert c.access.get(loc) == data
    finally:
        c.close()
