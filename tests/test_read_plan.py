"""The EC read plan (access._read_blob_ec): a blob the routing table already
shows degraded is read in ONE round and waited for ONCE.

  direct     every in-window data unit is routed and answers
  one_round  an in-window data unit is not routed: no direct phase; the live
             in-window data shards and the parities that make N are launched
             together, exactly N reads, the stage thread woken once
  two_round  a ROUTED unit failed or hung in the direct phase

Deployments: EC12P4 with a node down (benchmark cell az1.get16m-nodedown),
EC16P20L2 / EC6P10L2 with an AZ dark (az2.get16m-azdown)."""

import sys
import threading
import time

import numpy as np
import pytest

from chubaofs_tpu.blobstore import access as access_mod
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
from chubaofs_tpu.utils.exporter import registry

from test_azdown import FaultyUnits, az_nodes, survivor_plan

MiB = 1 << 20
# deployment -> (MiniCluster layout, how the outage is made, object sizes)
DEPLOYMENTS = {
    "az1-node1-down": (dict(n_nodes=9, disks_per_node=2), ("node", 1), [5 * MiB, 4 * MiB + 1]),
    "az2-az0-dark": (dict(n_nodes=12, disks_per_node=4, azs=2), ("az", 0), [5 * MiB, 65536]),
    "az2-az1-dark": (dict(n_nodes=12, disks_per_node=4, azs=2), ("az", 1), [5 * MiB, 65536]),
}


class Deployment:
    def __init__(self, name, root):
        layout, self.outage, self.sizes = DEPLOYMENTS[name]
        self.cluster = c = MiniCluster(root, **layout)
        for switch in ("shard_repair", "disk_repair"):
            c.scheduler.switches.set(switch, False)
        self.objects = {}
        for size in self.sizes:
            data = np.random.default_rng([35, size]).bytes(size)
            self.objects[size] = (c.access.put(data), data)
        self.real = dict(c.nodes)

    def darken(self, nodes=None):
        """Drop nodes from the routing table (default: the deployment's
        outage), as benchmark/deploy.py node_down does."""
        c = self.cluster
        if nodes is None:
            kind, which = self.outage
            nodes = [which] if kind == "node" else az_nodes(c, which)
        for n in nodes:
            c.nodes.pop(n, None)

    def restore(self):
        self.cluster.nodes.clear()
        self.cluster.nodes.update(self.real)

    def unrouted(self, blob):
        vol = self.cluster.cm.get_volume(blob.vid)
        return {u.index for u in vol.units if u.node_id not in self.cluster.nodes}


@pytest.fixture(scope="module", params=list(DEPLOYMENTS))
def dep(request, tmp_path_factory):
    d = Deployment(request.param, str(tmp_path_factory.mktemp(request.param)))
    yield d
    d.cluster.close()


@pytest.fixture
def down(dep):
    dep.darken()
    yield dep
    dep.restore()


def plan_counts():
    reg = registry("access")
    return {p: reg.counter("read_plan_total", {"plan": p}).value for p in access_mod.READ_PLANS}


def plans_since(before):
    return {p: v - before[p] for p, v in plan_counts().items()}


class Reads:
    """Every foreground shard read the access layer makes (the background
    probes pass count=False): (stripe index, offset, size, routed)."""

    def __init__(self, access):
        self.calls, self._access, self._sound = [], access, access._read_shard

    def __enter__(self):
        def recording(vol, idx, bid, offset, size, count=True):
            if count:
                self.calls.append((bid, idx, offset, size, vol.units[idx].node_id in self._access.nodes))
            return self._sound(vol, idx, bid, offset, size, count)

        self._access._read_shard = recording
        return self

    def __exit__(self, *exc):
        del self._access._read_shard

    def of(self, blob):
        return [c[1:] for c in self.calls if c[0] == blob.bid]


def in_window(t, blob, offset=0, size=None):
    """Data shard indices the byte range of one blob touches."""
    k = t.shard_size(blob.size)
    size = blob.size - offset if size is None else size
    return list(range(offset // k, (offset + size - 1) // k + 1))


# -- whole objects: bytes, reads, plans, the decode's survivor set ---------------


def test_a_healthy_cluster_reads_direct(dep):
    for size, (loc, data) in dep.objects.items():
        before = plan_counts()
        with Reads(dep.cluster.access) as reads:
            assert dep.cluster.access.get(loc) == data
        t = get_tactic(loc.code_mode)
        assert plans_since(before) == {"direct": len(loc.blobs), "one_round": 0, "two_round": 0}
        for b in loc.blobs:
            assert sorted(r[0] for r in reads.of(b)) == in_window(t, b)


def test_degraded_blob_is_read_in_n_reads_one_round_none_unrouted(down):
    dep = down
    calls = []
    sound = dep.cluster.codec.decode_rows
    dep.cluster.codec.decode_rows = lambda n, m, present, s, want: (
        calls.append((list(present), list(want))) or sound(n, m, present, s, want))
    try:
        for size, (loc, data) in dep.objects.items():
            t = get_tactic(loc.code_mode)
            del calls[:]
            before = plan_counts()
            with Reads(dep.cluster.access) as reads:
                assert dep.cluster.access.get(loc) == data
            degraded, decodes = 0, []
            for b in loc.blobs:
                dark = dep.unrouted(b)
                want = [i for i in in_window(t, b) if i in dark]
                got = reads.of(b)
                assert all(routed for *_, routed in got), "a read was handed to an unrouted unit"
                assert len({r[0] for r in got}) == len(got), "a shard was read twice"
                if not want:
                    assert sorted(r[0] for r in got) == in_window(t, b)
                    continue
                degraded += 1
                # exactly N reads (az1: 12, not 13): every live data shard,
                # then parities in index order
                live = [i for i in range(t.N + t.M) if i not in dark]
                present = sorted(i for i in live if i not in want)[: t.N]
                assert sorted(r[0] for r in got) == present and len(got) == t.N
                decodes.append((present, want))
            assert degraded, "the outage degraded no blob of this object"
            assert plans_since(before) == {
                "direct": len(loc.blobs) - degraded, "one_round": degraded, "two_round": 0}
            assert sorted(calls) == sorted(decodes)
            if dep.outage[0] == "az":
                # the survivor set tests/test_azdown.py holds the decode to
                full = [(p, w) for p, w in decodes if len(w) == t.N // 2]
                assert full and all((p, w) == survivor_plan(t, dep.outage[1]) for p, w in full)
    finally:
        del dep.cluster.codec.decode_rows


def test_az1_last_data_shard_is_read_once_over_the_decode_window(tmp_path):
    """A 4 MiB EC12P4 blob ends 8 bytes short of shard 11's end: the shard's
    own sub-window is [0, 349518), the decode window [0, 349526). One read
    over the hull serves the body and the decode (read_amp 13 / 12 -> 1)."""
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        data = np.random.default_rng(35).bytes(4 * MiB)
        loc = c.access.put(data)
        (blob,) = loc.blobs
        t = get_tactic(loc.code_mode)
        k = t.shard_size(blob.size)
        assert (t.N, k, blob.size - 11 * k) == (12, 349526, 349518)
        vol = c.cm.get_volume(blob.vid)
        c.nodes.pop(vol.units[2].node_id)
        read = registry("access").counter("read_bytes", {"kind": "shards_read"})
        r0 = read.value
        with Reads(c.access) as reads:
            assert c.access.get(loc) == data
        assert len(reads.calls) == 12 and read.value - r0 == 12 * k
        assert all((off, size) == (0, k) for _, _, off, size, _ in reads.calls)
    finally:
        c.close()


# -- ranges ------------------------------------------------------------------------


def ranges_of(loc):
    """Ranges of the first blob: inside one shard, across shards, edge to
    edge, into the next blob; for each the plan of the first blob."""
    t = get_tactic(loc.code_mode)
    b = loc.blobs[0]
    k = t.shard_size(b.size)
    n_in = len(in_window(t, b))
    out = [(7, 100), (k - 50, 100), (k + 7, 2 * k), (2 * k + 5, k - 5), (0, b.size)]
    if n_in > 4:
        out += [(3 * k + 7, 2 * k), ((n_in - 1) * k - 9, 30)]
    if len(loc.blobs) > 1:
        out.append((b.size - 3, 2000))
    return [(off, min(size, loc.size - off)) for off, size in out]


def test_ranged_gets_are_byte_equal_and_planned_by_what_they_touch(down):
    dep = down
    for size, (loc, data) in dep.objects.items():
        t = get_tactic(loc.code_mode)
        for off, n in ranges_of(loc):
            before = plan_counts()
            with Reads(dep.cluster.access) as reads:
                assert dep.cluster.access.get(loc, off, n) == data[off: off + n], (size, off, n)
            assert all(routed for *_, routed in reads.calls)
            want, at = {"direct": 0, "one_round": 0, "two_round": 0}, 0
            for b in loc.blobs:
                lo, hi = max(off, at), min(off + n, at + b.size)
                if lo < hi:
                    touched = in_window(t, b, lo - at, hi - lo)
                    degraded = bool(set(touched) & dep.unrouted(b))
                    want["one_round" if degraded else "direct"] += 1
                    got = reads.of(b)
                    assert len({r[0] for r in got}) == len(got), "a shard was read twice"
                    assert len(got) == (t.N if degraded else len(touched)), (off, n)
                at += b.size
            assert plans_since(before) == want, (size, off, n)


def test_a_range_that_misses_the_dark_shards_reads_direct(down):
    dep = down
    loc, data = dep.objects[dep.sizes[0]]
    t = get_tactic(loc.code_mode)
    b = loc.blobs[0]
    k = t.shard_size(b.size)
    live = [i for i in in_window(t, b) if i not in dep.unrouted(b)]
    i = live[0]
    before = plan_counts()
    with Reads(dep.cluster.access) as reads:
        assert dep.cluster.access.get(loc, i * k + 3, k - 3) == data[i * k + 3: (i + 1) * k]
    assert plans_since(before) == {"direct": 1, "one_round": 0, "two_round": 0}
    assert reads.of(b) == [(i, 3, k - 3, True)]


# -- faults inside the round ----------------------------------------------------


@pytest.fixture
def az0_dark(tmp_path_factory):
    d = Deployment("az2-az0-dark", str(tmp_path_factory.mktemp("faults")))
    d.darken()
    yield d
    d.cluster.close()


def with_faults(dep, **kw):
    for n in list(dep.cluster.nodes):
        dep.cluster.nodes[n] = FaultyUnits(dep.cluster.nodes[n], **kw)


@pytest.mark.parametrize("fail_idx", [26, 9], ids=["a_parity", "a_live_data_shard"])
def test_a_routed_unit_that_fails_in_the_round_launches_exactly_one_replacement(az0_dark, fail_idx):
    dep = az0_dark
    loc, data = dep.objects[5 * MiB]
    t = get_tactic(loc.code_mode)
    with_faults(dep, fail_idx=[fail_idx], hang_idx=[], hang_s=0)
    calls = []
    sound = dep.cluster.codec.decode_rows
    dep.cluster.codec.decode_rows = lambda n, m, present, s, want: (
        calls.append((list(present), list(want))) or sound(n, m, present, s, want))
    before = plan_counts()
    with Reads(dep.cluster.access) as reads:
        assert dep.cluster.access.get(loc) == data
    assert plans_since(before) == {"direct": 0, "one_round": len(loc.blobs), "two_round": 0}
    for b in loc.blobs:
        # the round's 16 (8..15, 26..33) and the one replacement, 34
        assert sorted(r[0] for r in reads.of(b)) == list(range(8, 16)) + list(range(26, 35))
    survivors = sorted((set(range(8, 16)) | set(range(26, 35))) - {fail_idx})
    want = sorted(set(range(8)) | ({fail_idx} if fail_idx < t.N else set()))
    assert calls == [(survivors, want)] * len(loc.blobs)


def test_a_routed_unit_that_hangs_in_the_round_is_hedged_after_read_deadline(az0_dark, monkeypatch):
    dep = az0_dark
    loc, data = dep.objects[5 * MiB]
    monkeypatch.setattr(dep.cluster.access, "read_deadline", 0.3)
    with_faults(dep, fail_idx=[], hang_idx=[10], hang_s=5.0)
    t0 = time.monotonic()
    with Reads(dep.cluster.access) as reads:
        assert dep.cluster.access.get(loc) == data
    took = time.monotonic() - t0
    # the hedge goes out at read_deadline and the GET does not wait the hang out
    assert 0.3 <= took < 4.0, took
    for b in loc.blobs:
        assert sorted(r[0] for r in reads.of(b)) == list(range(8, 16)) + list(range(26, 35))


def test_a_failed_edge_shard_wider_than_the_decode_window_falls_to_the_full_path(tmp_path):
    """Range over shards 3 [7, k), 4 [0, k), 5 [0, 7) with 5 unrouted: the
    decode window is [0, 7). Shard 3 (routed) fails inside the round: its
    bytes lie outside the columns the survivors were read over, so the
    full-stripe path rebuilds the range."""
    c = MiniCluster(str(tmp_path), n_nodes=16, disks_per_node=1)
    try:
        data = np.random.default_rng(36).bytes(4 * MiB)
        loc = c.access.put(data, code_mode=CodeMode.EC12P4)
        (blob,) = loc.blobs
        k = get_tactic(loc.code_mode).shard_size(blob.size)
        vol = c.cm.get_volume(blob.vid)
        c.nodes.pop(vol.units[5].node_id)
        off, n = 3 * k + 7, 2 * k
        with Reads(c.access) as reads:
            assert c.access.get(loc, off, n) == data[off: off + n]
        # clean: 3 and 4 once over the hull [0, k), the other ten over [0, 7)
        assert sorted(r[:3] for r in reads.of(blob)) == sorted(
            [(3, 0, k), (4, 0, k)] + [(i, 0, 7) for i in (0, 1, 2, 6, 7, 8, 9, 10, 11, 12)])
        for nid in list(c.nodes):
            c.nodes[nid] = FaultyUnits(c.nodes[nid], fail_idx=[3], hang_idx=[], hang_s=0)
        before = plan_counts()
        assert c.access.get(loc, off, n) == data[off: off + n]
        assert plans_since(before) == {"direct": 0, "one_round": 1, "two_round": 0}
    finally:
        c.close()


def test_a_routed_failure_on_a_routed_stripe_is_the_two_round_plan(dep):
    loc, data = dep.objects[dep.sizes[0]]
    with_faults(dep, fail_idx=[1], hang_idx=[], hang_s=0)
    try:
        before = plan_counts()
        assert dep.cluster.access.get(loc) == data
        assert plans_since(before) == {"direct": 0, "one_round": 0, "two_round": len(loc.blobs)}
    finally:
        dep.restore()


# -- the round's single wait -------------------------------------------------------


class CountingEvent(threading.Event):
    """threading.Event that counts the waits of the instances made inside
    _gather_survivors (threads make Events of their own while they start)."""

    rounds: list = []

    def __init__(self):
        super().__init__()
        self.waits = 0
        if sys._getframe(1).f_code.co_name == "_gather_survivors":
            CountingEvent.rounds.append(self)

    def wait(self, timeout=None):
        self.waits += 1
        return super().wait(timeout)


def test_a_clean_round_wakes_its_stage_thread_once(down, monkeypatch):
    dep = down
    loc, data = dep.objects[dep.sizes[0]]
    t = get_tactic(loc.code_mode)
    degraded = sum(1 for b in loc.blobs if set(in_window(t, b)) & dep.unrouted(b))
    CountingEvent.rounds = []
    monkeypatch.setattr(threading, "Event", CountingEvent)
    assert dep.cluster.access.get(loc) == data
    monkeypatch.undo()
    assert len(CountingEvent.rounds) == degraded > 0
    assert [e.waits for e in CountingEvent.rounds] == [1] * degraded


def test_concurrent_degraded_gets_under_a_short_switch_interval(down):
    """The round's callbacks run on the read pool's sixteen threads while the
    stage threads drain them: more readers than cores, a switch interval short
    enough to cut the hand-overs anywhere, every GET byte-equal and done."""
    from concurrent.futures import ThreadPoolExecutor

    dep = down
    loc, data = dep.objects[dep.sizes[0]]
    before = plan_counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=12) as pool:
            futs = [pool.submit(dep.cluster.access.get, loc) for _ in range(36)]
            assert all(f.result(timeout=120) == data for f in futs)
    finally:
        sys.setswitchinterval(interval)
    t = get_tactic(loc.code_mode)
    degraded = sum(1 for b in loc.blobs if set(in_window(t, b)) & dep.unrouted(b))
    assert plans_since(before)["one_round"] == 36 * degraded and plans_since(before)["two_round"] == 0


# -- a gather that cannot reach N returns when its last read does --------------------


@pytest.fixture(scope="module")
def lrc_deep(tmp_path_factory):
    """EC6P3L3 with globals 0, 1, 2, 4 lost (routed units that error): the
    window gather finds 3 candidates for the 4 it wants, the full-stripe
    gather 5 for 6, and AZ-local recovery wins the rest back. Every read
    SUCCEEDS or fails at once; nothing hangs."""
    c = MiniCluster(str(tmp_path_factory.mktemp("lrc_deep")), n_nodes=6, disks_per_node=2, azs=3)
    data = np.random.default_rng(37).bytes(96 * 1024)
    loc = c.access.put(data, code_mode=CodeMode.EC6P3L3)
    (blob,) = loc.blobs
    vol = c.cm.get_volume(blob.vid)
    for i in (0, 1, 2, 4):
        c.nodes[vol.units[i].node_id].lose_shard(vol.units[i].vuid, blob.bid)
    gathers = []
    sound = c.access._gather_survivors

    def timed(vol, bid, candidates, needed, lo, n, windows=None):
        t0 = time.monotonic()
        got, failed = sound(vol, bid, candidates, needed, lo, n, windows)
        gathers.append((time.monotonic() - t0, needed, len(got), len(candidates)))
        return got, failed

    c.access._gather_survivors = timed
    t0 = time.monotonic()
    assert c.access.get(loc) == data
    yield {"get_s": time.monotonic() - t0, "gathers": gathers, "deadline": c.access.read_deadline}
    c.close()


@pytest.mark.parametrize("which", ["window", "full"])
def test_a_gather_short_of_n_does_not_sleep_to_read_deadline(lrc_deep, which):
    """All its reads succeed and they are fewer than it wants: nothing fails,
    the `needed`-th success never comes, and the gather still returns with
    its last read (not at the earliest read's read_deadline)."""
    window, full = lrc_deep["gathers"]
    took, needed, n_got, n_candidates = window if which == "window" else full
    assert (needed, n_got) == ((4, 3) if which == "window" else (6, 5))
    assert n_got < needed
    assert took < lrc_deep["deadline"] / 6, (took, lrc_deep)


def test_a_deep_damage_get_takes_no_read_deadline(lrc_deep):
    assert lrc_deep["get_s"] < lrc_deep["deadline"] / 3, lrc_deep


@pytest.mark.parametrize("routed,needed", [(3, 5), (1, 4), (0, 2)])
def test_a_gather_with_fewer_routed_candidates_than_needed_returns_at_once(down, routed, needed):
    """Candidates the routing table does not hold are failures never
    launched; where the routed ones cannot make `needed`, the gather is back
    when they are, with what they read."""
    dep = down
    loc, _ = dep.objects[dep.sizes[0]]
    blob = loc.blobs[0]
    t = get_tactic(loc.code_mode)
    vol = dep.cluster.cm.get_volume(blob.vid)
    dark = sorted(dep.unrouted(blob))
    live = [i for i in range(t.N + t.M) if i not in dark]
    t0 = time.monotonic()
    got, failed = dep.cluster.access._gather_survivors(vol, blob.bid, dark[:2] + live[:routed], needed, 0, 64)
    assert time.monotonic() - t0 < dep.cluster.access.read_deadline / 6
    assert sorted(got) == live[:routed] and failed == dark[:2]
    assert all(len(v) == 64 for v in got.values())
