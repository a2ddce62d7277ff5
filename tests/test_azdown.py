"""The production 2-AZ LRC deployment with one whole AZ dark
(benchmark/configs/az2-ec16p20l2-azdown.json): the state EC16P20L2's 20
parities are paid for. One AZ alone keeps 8 data + 10 global-parity shards of
every stripe (EC6P10L2: 3 + 5), so every object still reads back, half of its
data rebuilt by decode; no PUT can reach its quorum; repair is held.

The plain reference is benchmark/reference.py (encode) and
benchmark/reference_decode.py (Gauss-Jordan over GF(2^8)), loaded by path:
neither imports anything of the program."""

import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

from chubaofs_tpu.blobstore.access import QuorumError
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.blobstore.clustermgr import parse_vuid
from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
from chubaofs_tpu.codec.service import CodecService
from chubaofs_tpu.utils.exporter import registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
MODES = ["EC16P20L2", "EC6P10L2"]


def _load(name):
    """A module of benchmark/ by path (reference_decode imports reference)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_" + name, os.path.join(BENCH, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(BENCH)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _json("configs", "az2-ec16p20l2-azdown.json")
TRAFFIC = _json("traffic", "get16m-azdown.json")
LAYOUT = dict(n_nodes=CONFIG["layout"]["nodes"], disks_per_node=CONFIG["layout"]["disks_per_node"],
              azs=CONFIG["layout"]["azs"])


def survivor_plan(t, dark_az):
    """(present, want) of the decode a whole-blob GET makes with ``dark_az``
    unreachable: wanted are the dark AZ's data shards; the survivors are the
    live AZ's data shards (read by the direct phase, reused) and, in index
    order, as many of its global parities as make N."""
    dark = set(t.shards_in_az(dark_az))
    want = [i for i in range(t.N) if i in dark]
    live = [i for i in range(t.N + t.M) if i not in dark]
    return sorted(live[: t.N]), want


def az_nodes(cluster, az):
    return sorted({d.node_id for d in cluster.cm.disks.values() if d.az == az})


@pytest.fixture(scope="module")
def refs():
    return _load("reference"), _load("reference_decode")


@pytest.fixture(scope="module")
def codec():
    svc = CodecService()
    yield svc
    svc.close()


# -- the codec against the plain reference ------------------------------------

# a blob that gives the published shard width (EC16P20L2: a full 4 MiB blob,
# 262,144 B a shard; EC6P10L2: its largest, 1 MiB, 174,763 B) and one that
# gives the 2 KiB minimum shard
WIDTHS = {"EC16P20L2": (4194304, 262144), "EC6P10L2": (1048576, 174763)}


@pytest.mark.parametrize("dark_az", [0, 1])
@pytest.mark.parametrize("width", ["published", "min_shard"])
@pytest.mark.parametrize("mode_name", MODES)
def test_decode_rows_equals_reference_decode_equals_bytes_put(refs, codec, mode_name, width, dark_az):
    reference, reference_decode = refs
    mode, code = CONFIG["modes"][mode_name], CONFIG["code"]
    t = get_tactic(mode_name)
    blob_size, k = WIDTHS[mode_name] if width == "published" else (3000, code["min_shard_size"])
    assert t.shard_size(blob_size) == k
    blob = np.random.default_rng([34, dark_az, k]).bytes(blob_size)
    stripe = reference.encode(blob, mode, code)
    present, want = survivor_plan(t, dark_az)
    assert len(present) == t.N and len(want) == t.N // 2
    assert not set(present) & set(t.shards_in_az(dark_az))
    got = np.asarray(codec.decode_rows(t.N, t.M, present, stripe[present], want).result())
    ref = reference_decode.solve(present, stripe[present], want, mode, code)
    assert got.shape == ref.shape == (len(want), k)
    assert np.array_equal(got, ref), "decode_rows differs from the plain reference"
    assert np.array_equal(ref, stripe[want]), "the reference does not return the bytes put"
    # the whole blob from what the dark AZ leaves readable, local parity and all
    left = [None if t.az_of_shard(i) == dark_az else stripe[i].tobytes() for i in range(t.total)]
    assert reference_decode.decode(left, blob_size, mode, code) == blob


def test_reference_decode_refuses_a_stripe_below_n(refs):
    reference, reference_decode = refs
    mode, code = CONFIG["modes"]["EC6P10L2"], CONFIG["code"]
    stripe = reference.encode(b"x" * 5000, mode, code)
    left = [s.tobytes() if i in (0, 1, 2, 6, 7) else None for i, s in enumerate(stripe)]
    with pytest.raises(ValueError, match="only 5 global shards"):
        reference_decode.decode(left, 5000, mode, code)


# -- the configuration and its traffic file say the same outage ---------------


def test_configuration_is_az2_with_az0_dark():
    base = _json("configs", "az2-ec16p20l2.json")
    for key in ("layout", "policies", "modes", "max_blob_size", "cache_plane", "code", "scale"):
        assert CONFIG[key] == base[key], key
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == "az2.get16m-azdown")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "get16m-azdown", 1)
    assert TRAFFIC["nodes_down"] == CONFIG["failure"]["nodes"]
    assert TRAFFIC["switches_off"] == CONFIG["task_switches_off"] == ["shard_repair", "disk_repair"]
    p = TRAFFIC["params"]
    assert (p["streams"], p["object_bytes"], p["objects"]) == (
        CONFIG["assumed"]["reader_streams"], CONFIG["assumed"]["object_bytes"], CONFIG["assumed"]["objects"])
    assert TRAFFIC["load_disk_bytes"] == p["objects"] * p["object_bytes"] * 2.5


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = MiniCluster(str(tmp_path_factory.mktemp("az2")), **LAYOUT)
    for name in CONFIG["task_switches_off"]:
        c.scheduler.switches.set(name, False)
    yield c
    c.close()


@pytest.fixture
def dark(cluster):
    """darken(az): drop the AZ's nodes from the routing table, as
    benchmark/deploy.py node_down and chaos/scheduler.py _kill do (the engines
    stay open here: they are routed again after the test)."""
    real = dict(cluster.nodes)

    def darken(az):
        cluster.nodes.clear()
        cluster.nodes.update({n: e for n, e in real.items() if n not in az_nodes(cluster, az)})

    yield darken
    cluster.nodes.clear()
    cluster.nodes.update(real)


def test_traffic_nodes_down_are_az0_and_hold_every_volumes_az0_units(cluster):
    f = CONFIG["failure"]
    assert f["nodes"] == az_nodes(cluster, f["az_down"])
    assert f["disks"] == sum(1 for d in cluster.cm.disks.values() if d.az == f["az_down"])
    for mode_name in MODES:
        t = get_tactic(mode_name)
        vol = cluster.cm.alloc_volume(int(CodeMode[mode_name]))
        on_dark = [u.index for u in vol.units if u.node_id in f["nodes"]]
        assert on_dark == t.shards_in_az(f["az_down"])
    assert f["shards_a_stripe_lost"] == len(get_tactic("EC16P20L2").shards_in_az(0)) == 19


# -- the served path ----------------------------------------------------------------

SIZES = [65536, 1048576, 5242880, 16777217]


def _counter(name, labels):
    return registry("access").counter(name, labels)


@pytest.fixture(scope="module")
def objects(cluster):
    """One object a size, put on the healthy cluster: (location, bytes)."""
    out = {}
    for size in SIZES:
        data = np.random.default_rng([34, size]).bytes(size)
        out[size] = (cluster.access.put(data), data)
    return out


def _data_bytes(loc):
    """Bytes of the stripes' data shards (the blob and its zero padding)."""
    t = get_tactic(loc.code_mode)
    return sum(t.N * t.shard_size(b.size) for b in loc.blobs)


@pytest.mark.parametrize("dark_az", [0, 1])
@pytest.mark.parametrize("size", SIZES)
def test_get_with_an_az_dark_is_byte_equal_and_decodes_half(cluster, objects, dark, refs, size, dark_az):
    loc, data = objects[size]
    t = get_tactic(loc.code_mode)
    assert CodeMode(loc.code_mode).name == ("EC6P10L2" if size <= 1048576 else "EC16P20L2")
    decoded = _counter("read_bytes", {"kind": "decoded"})
    read = _counter("read_bytes", {"kind": "shards_read"})
    calls = []
    sound = cluster.codec.decode_rows

    def recording(n, m, present, survivors, want):
        calls.append((n, m, list(present), list(want)))
        return sound(n, m, present, survivors, want)

    dark(dark_az)
    cluster.codec.decode_rows = recording
    try:
        d0, r0 = decoded.value, read.value
        assert cluster.access.get(loc) == data
        d1, r1 = decoded.value, read.value
    finally:
        del cluster.codec.decode_rows
    # the dark AZ's data shards the blob's bytes reach are rebuilt, over the
    # columns those bytes fill: exactly half of the stripe's data wherever a
    # blob reaches all N data shards (the 1-byte tail blob reaches shard 0 alone)
    plan, want_decoded = [], 0
    for b in loc.blobs:
        k = t.shard_size(b.size)
        present, want = survivor_plan(t, dark_az)
        want = [i for i in want if i * k < b.size]
        if want:
            plan.append((t.N, t.M, present, want))
            want_decoded += len(want) * min(k, b.size - want[0] * k)
        if b.size > (t.N - 1) * k:
            assert len(want) * min(k, b.size - want[0] * k) == t.N * k // 2
    assert d1 - d0 == want_decoded
    # one windowed decode a blob (three blobs are in flight: any order), on
    # the survivor set the codec test used
    assert sorted(calls) == sorted(plan)
    # a GET reads N shards a blob from blobnodes, none twice: one stripe's
    # worth of data bytes. (A blob that ends short of its last data shard has
    # that shard's direct read cover less than the decode window: it is read
    # again, whole.)
    shards = [t.shard_size(b.size) for b in loc.blobs]
    if all(b.size == t.N * k for b, k in zip(loc.blobs, shards)):
        assert r1 - r0 == _data_bytes(loc)
    assert d1 - d0 <= r1 - r0 <= _data_bytes(loc) + sum(shards)
    # the plain reference returns the same object from what the live AZ stores
    reference_decode = refs[1]
    mode = CONFIG["modes"][CodeMode(loc.code_mode).name]
    rebuilt = b""
    for b in loc.blobs:
        units = cluster.cm.get_volume(b.vid).units
        left = [cluster.nodes[u.node_id].get_shard(u.vuid, b.bid) if u.node_id in cluster.nodes else None
                for u in units]
        assert [s is None for s in left] == [t.az_of_shard(i) == dark_az for i in range(t.total)]
        rebuilt += reference_decode.decode(left, b.size, mode, CONFIG["code"])
    assert rebuilt == data


@pytest.mark.parametrize("dark_az", [0, 1])
def test_ranged_gets_across_dark_and_live_shards(cluster, objects, dark, dark_az):
    """Ranges that start and end inside dark shards, inside live shards, and
    that cross from one to the other (and from blob to blob)."""
    loc, data = objects[16777217]
    k = get_tactic(loc.code_mode).shard_size(loc.blobs[0].size)
    assert k == 262144
    blob = loc.blobs[0].size
    ranges = [
        (100, 1000),                      # inside shard 0 (AZ 0)
        (3 * k + 7, 2 * k),               # shards 3..5, all AZ 0
        (9 * k + 1, 3 * k),               # shards 9..12, all AZ 1
        (6 * k + 5000, 4 * k),            # 6, 7 in AZ 0 into 8..10 in AZ 1
        (15 * k + 10, k),                 # the last shard of blob 0 into blob 1
        (blob - 3, 2 * blob + 11),        # three blobs
        (len(data) - 1, 1),               # the 1-byte tail blob (2 KiB minimum shard)
    ]
    dark(dark_az)
    for off, size in ranges:
        assert cluster.access.get(loc, off, size) == data[off: off + size], (off, size)


@pytest.mark.parametrize("dark_az", [0, 1])
@pytest.mark.parametrize("size", [65536, 5242880])
def test_put_with_an_az_dark_is_refused(cluster, dark, dark_az, size):
    """18 (EC6P10L2: 8) of the globals can be written, the put quorum is 34
    (14): no acknowledgement, no location."""
    dark(dark_az)
    loc = None
    with pytest.raises(QuorumError, match="quorum"):
        loc = cluster.access.put(np.random.default_rng(size).bytes(size))
    assert loc is None
    cluster.access.clear_punishments()  # the failed writes punished the dark AZ's disks


# -- the read plan knows the routing table ------------------------------------


def _repair_indices(cluster, loc, since):
    """bad_idx the shard-repair topic received a blob of ``loc`` after message
    ``since``, once the GET's background probes are done."""
    deadline = time.monotonic() + 30
    while cluster.access._probing and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not cluster.access._probing
    msgs = cluster.proxy.topics["shard_repair"].consume("test_azdown", 1 << 30)[since:]
    got = {(b.vid, b.bid): set() for b in loc.blobs}
    for m in msgs:
        assert m["reason"] in ("get_miss", "get_probe")
        got[(m["vid"], m["bid"])] |= set(m["bad_idx"])
    return list(got.values())


@pytest.mark.parametrize("dark_az", [0, 1])
def test_dark_az_costs_no_unrouted_read_and_repair_hears_the_same(cluster, objects, dark, dark_az):
    """No read is handed to the pool for a unit whose node is not routed (a
    plan blind to routing submits 18 a blob), yet the repair topic still
    receives every global index of the dark AZ for every blob, as it did when
    those reads were made and failed."""
    loc, data = objects[5242880]
    t = get_tactic(loc.code_mode)
    unrouted = _counter("read_fail", {"reason": "no_node"})
    since = len(cluster.proxy.topics["shard_repair"].consume("test_azdown", 1 << 30))
    dark(dark_az)
    u0 = unrouted.value
    assert cluster.access.get(loc) == data
    heard = _repair_indices(cluster, loc, since)
    assert unrouted.value - u0 == 0
    dark_globals = {i for i in t.shards_in_az(dark_az) if i < t.N + t.M}
    assert len(dark_globals) == 18 and heard == [dark_globals] * len(loc.blobs)


class FaultyUnits:
    """Pass-through blobnode whose reads of some stripe positions fail, and of
    others hang: a routed node with a bad disk and a wedged one."""

    def __init__(self, inner, fail_idx, hang_idx, hang_s):
        self._inner, self._fail, self._hang, self._hang_s = inner, set(fail_idx), set(hang_idx), hang_s

    def get_shard(self, vuid, bid, offset=0, size=None):
        idx = parse_vuid(vuid)[1]
        if idx in self._fail:
            raise RuntimeError("disk error")
        if idx in self._hang:
            time.sleep(self._hang_s)
        return self._inner.get_shard(vuid, bid, offset=offset, size=size)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_hedged_replacement_still_works_when_a_routed_node_fails(cluster, objects, dark, monkeypatch):
    """AZ 0 dark leaves EC16P20L2 two global shards to spare. Parity 26 on a
    routed node fails and parity 27 hangs past read_deadline: the gather
    replaces the one on its failure and hedges the other, with 34 and 35, the
    last two live globals; the failed read is reported to repair with the dark
    AZ's units."""
    loc, data = objects[5242880]
    t = get_tactic(loc.code_mode)
    since = len(cluster.proxy.topics["shard_repair"].consume("test_azdown", 1 << 30))
    monkeypatch.setattr(cluster.access, "read_deadline", 0.3)
    calls = []
    sound = cluster.codec.decode_rows
    monkeypatch.setattr(cluster.codec, "decode_rows",
                        lambda n, m, present, s, want: calls.append(list(present)) or sound(n, m, present, s, want))
    dark(0)
    for n in list(cluster.nodes):
        cluster.nodes[n] = FaultyUnits(cluster.nodes[n], fail_idx=[26], hang_idx=[27], hang_s=1.5)
    assert cluster.access.get(loc) == data
    assert calls == [list(range(8, 16)) + list(range(28, 36))] * len(loc.blobs)
    heard = _repair_indices(cluster, loc, since)
    dark_globals = {i for i in t.shards_in_az(0) if i < t.N + t.M}
    assert all(h >= dark_globals | {26} and h <= dark_globals | {26, 27} for h in heard)
