"""A GET's body is built once (ISSUE 31): `Access.get_buffer` allocates one
buffer at the range's length and every blob read writes at its final offset
in it. A 3-blob object is read whole and by ranges that start and end inside
blobs on every path that ends in that writer (healthy, windowed decode, the
full-stripe decode, an LRC local recovery, the cache plane, a hot copy); the
gateway hands the filled buffer to `Response` as it is."""

import json
import urllib.parse

import numpy as np
import pytest

from chubaofs_tpu.blobstore.cache import BlobCache
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.gateway import build_router
from chubaofs_tpu.codec.codemode import CodeMode
from chubaofs_tpu.rpc.router import parse_request
from chubaofs_tpu.utils.exporter import registry

BLOB = 96 * 1024
SIZE = 2 * BLOB + 40_000  # three blobs, the last one short
# (offset, length or None): whole; blob 0 into blob 1; mid-shard of blob 1
# into blob 2; inside one shard of blob 1; the last byte
RANGES = [(0, None), (BLOB - 5_000, 30_000), (BLOB + 12_345, BLOB + 20_000),
          (BLOB + 3_000, 2_000), (SIZE - 1, 1)]


def count(reg: str, name: str, labels: dict | None = None) -> float:
    return registry(reg).counter(name, labels).value


def lose(c, blob, idxs):
    vol = c.cm.get_volume(blob.vid)
    for i in idxs:
        c.nodes[vol.units[i].node_id].lose_shard(vol.units[i].vuid, blob.bid)


def record(access, name: str, calls: list):
    inner = getattr(access, name)

    def wrapper(*a, **k):
        calls.append(name)
        return inner(*a, **k)

    setattr(access, name, wrapper)


def healthy(root):
    return MiniCluster(root, n_nodes=9, disks_per_node=2), None, lambda c, loc: None


def node_down(root):
    """Node 1's shards of every stripe are decoded over the window's columns."""
    def damage(c, loc):
        c.nodes.pop(1).close()
    return MiniCluster(root, n_nodes=9, disks_per_node=2), None, damage


def full_stripe(root):
    """A regenerating stripe decodes whole (`_degraded_full`, no window)."""
    def damage(c, loc):
        for blob in loc.blobs:
            lose(c, blob, (2, 9))
    return MiniCluster(root, n_nodes=13, disks_per_node=2), CodeMode.RG6P6, damage


def lrc_local(root):
    """Four of EC6P3L3's nine globals gone: the window gather cannot reach
    six, AZ 1's and AZ 2's local stripes win one back each (AZ 0 lost two)."""
    def damage(c, loc):
        for blob in loc.blobs:
            lose(c, blob, (0, 1, 2, 4))
    return (MiniCluster(root, n_nodes=6, disks_per_node=2, azs=3),
            CodeMode.EC6P3L3, damage)


def cache_plane(root):
    cache = BlobCache(root + "-cache", mem_mb=8, disk_mb=32, promote_hits=0,
                      block_bytes=16 * 1024)
    return (MiniCluster(root, n_nodes=9, disks_per_node=2, cache=cache), None,
            lambda c, loc: None)


def hot_copy(root):
    """Every blob promoted into the Replica3 engine; each read below punches
    the cache first, so it goes through the hot tier."""
    cache = BlobCache(root + "-cache", mem_mb=8, disk_mb=32, promote_hits=3)

    def damage(c, loc):
        for _ in range(4):  # cross promote_hits
            c.access.get(loc)
        c.run_background_once()
        assert all(c.cm.hot_location(b.vid, b.bid) for b in loc.blobs)
    return (MiniCluster(root, n_nodes=9, disks_per_node=2, cache=cache), None,
            damage)


SCENARIOS = {f.__name__: f for f in (healthy, node_down, full_stripe,
                                     lrc_local, cache_plane, hot_copy)}


@pytest.fixture(scope="module", params=list(SCENARIOS))
def stored(request, tmp_path_factory):
    """(scenario, cluster, location, the bytes put, recorded internal calls)."""
    root = str(tmp_path_factory.mktemp(request.param))
    c, mode, damage = SCENARIOS[request.param](root)
    c.access.max_blob_size = BLOB
    data = np.random.default_rng(31).integers(0, 256, SIZE, dtype=np.uint8).tobytes()
    loc = c.access.put(data, code_mode=mode)
    assert [b.size for b in loc.blobs] == [BLOB, BLOB, 40_000]
    damage(c, loc)
    calls: list = []
    for name in ("_degraded_window", "_degraded_full", "_recover_locals_inplace"):
        record(c.access, name, calls)
    yield request.param, c, loc, data, calls
    c.close()


@pytest.mark.parametrize("offset,length", RANGES)
def test_body_equals_the_bytes_put(stored, offset, length):
    scenario, c, loc, data, calls = stored
    del calls[:]
    if scenario == "hot_copy":
        for b in loc.blobs:
            c.access.cache.invalidate(b.vid, b.bid)
    hits0, tier0 = count("cache", "hits"), count("cache", "tier_hits")
    want = data[offset:] if length is None else data[offset:offset + length]

    got = c.access.get(loc, offset, length)

    assert type(got) is bytes and got == want
    # the path the scenario names is the one that ran
    if scenario == "healthy":
        assert not calls
    elif scenario == "node_down":
        assert "_degraded_full" not in calls
        # a range, or a stripe, may have no data shard on node 1's two disks;
        # the whole object's three stripes have
        assert length is not None or "_degraded_window" in calls
    elif scenario == "full_stripe":
        # a range may miss the lost shard 2 (data); the whole object cannot
        assert "_degraded_window" not in calls
        assert length is not None or calls.count("_degraded_full") == 3
    elif scenario == "lrc_local":
        if offset == SIZE - 1:  # the last byte lies in shard 5: not lost
            assert not calls
        else:
            assert "_recover_locals_inplace" in calls and "_degraded_full" in calls
    elif scenario == "hot_copy":
        assert count("cache", "tier_hits") > tier0
    if scenario == "cache_plane":
        # the miss filled the blocks the range touches: the same range hits
        assert c.access.get(loc, offset, length) == want
        assert count("cache", "hits") > hits0


def test_serial_window_ends_in_the_same_writer(tmp_path):
    """`pipeline_window` 0 reads the segments one after the other on the
    caller's thread, into the same buffer."""
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        c.access.max_blob_size = BLOB
        data = np.random.default_rng(32).integers(0, 256, SIZE, dtype=np.uint8).tobytes()
        loc = c.access.put(data)
        c.access.pipeline_window = 0
        n0 = count("access", "get_readahead_prefetch")
        assert c.access.get(loc) == data
        assert c.access.get(loc, BLOB - 7, BLOB + 100) == data[BLOB - 7:2 * BLOB + 93]
        assert count("access", "get_readahead_prefetch") == n0
    finally:
        c.close()


def stage_count(name: str) -> float:
    return registry("trace").summary("stage_seconds", {"stage": name}).count


@pytest.mark.parametrize("route", ["post_get", "get_query", "get_range"])
def test_gateway_reply_is_the_buffer_the_reads_filled(tmp_path, route):
    """No whole-body copy between `Access` and `Response`: the reply's body
    is the buffer `_get` allocated and the reads filled, and each of the
    object's three blobs observed `access.assemble` once."""
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        c.access.max_blob_size = BLOB
        data = np.random.default_rng(33).integers(0, 256, SIZE, dtype=np.uint8).tobytes()
        token = c.access.put(data).to_json()
        bodies = []
        inner = c.access._get

        def spy(*a, **k):
            bodies.append(inner(*a, **k))
            return bodies[-1]

        c.access._get = spy
        router = build_router(c.access)
        quoted = urllib.parse.quote(token, safe="")
        if route == "post_get":
            req = parse_request("POST", "/get", {},
                                json.dumps({"location": token}).encode())
        elif route == "get_query":
            req = parse_request("GET", f"/get?location={quoted}", {}, b"")
        else:
            req = parse_request("GET", f"/get?location={quoted}",
                                {"Range": f"bytes=100-{SIZE - 101}"}, b"")
        n0 = stage_count("access.assemble")

        resp = router.dispatch(req)

        want = data[100:SIZE - 100] if route == "get_range" else data
        assert resp.status == (206 if route == "get_range" else 200)
        assert len(bodies) == 1 and isinstance(resp.body, memoryview)
        assert resp.body is bodies[0], "the reply is a copy of the body"
        assert len(resp.body) == len(want) and resp.body == want
        assert stage_count("access.assemble") - n0 == 3
    finally:
        c.close()
