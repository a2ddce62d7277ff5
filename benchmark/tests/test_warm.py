"""deploy.warm_decode / warm_encode on a fake cluster (no daemon, no device): a
unit is down where its node is not routed OR its disk is not NORMAL, the two
give the same shapes; the shapes a traffic file names (`warm.decode_shapes`,
`warm.encode_shapes`) are listed in the same result and reach the codec service
at exactly the batch counts asked; a cell that names none prints what it
always printed."""
import json
from concurrent.futures import Future
from types import SimpleNamespace

import pytest

import deploy

EC12P4, EC16P20L2 = 9, 3  # codec.codemode.CodeMode values; checked below by name


class Codec:
    """Counts what reaches the service: a 'batch' closes when max_batch jobs are in,
    as the real drain does under _exact_batches' hold."""
    max_batch, max_wait = 32, 0.0

    def __init__(self):
        self.calls, self.batches, self.jobs, self._open = [], 0, 0, 0

    def _job(self, *what):
        self.calls.append((self.max_batch, *what))
        self.jobs += 1
        self._open += 1
        if self._open == self.max_batch:
            self.batches, self._open = self.batches + 1, 0
        f = Future()
        f.set_result(None)
        return f

    def decode_rows(self, n, m, present, surv, want):
        assert surv.shape[0] == n == len(present) and not set(present) & set(want)
        return self._job("decode", n, m, len(want), surv.shape[1])

    def encode(self, n, m, data):
        return self._job("encode", n, m, data.shape[0], data.shape[1])

    def stats_snapshot(self):
        return {"batches": self.batches, "jobs": self.jobs}


def cluster(total, node_of, routed, broken=()):
    """One volume of `total` units, unit i on disk i of node node_of(i)."""
    units = [SimpleNamespace(index=i, disk_id=i, node_id=node_of(i), vuid=i) for i in range(total)]
    cm = SimpleNamespace(get_volume=lambda vid: SimpleNamespace(units=units),
                         disk_status=lambda d: "broken" if d in broken else "normal")
    dep = object.__new__(deploy.Deployment)
    dep.cluster = SimpleNamespace(codec=Codec(), cm=cm, nodes={n: object() for n in routed})
    return dep


def token(mode, size=4 << 20):
    return json.dumps({"code_mode": mode, "blobs": [{"vid": 1, "bid": 1, "size": size}]})


def test_the_modes_are_the_ones_named():
    from chubaofs_tpu.codec.codemode import CodeMode

    assert (CodeMode(EC12P4).name, CodeMode(EC16P20L2).name) == ("EC12P4", "EC16P20L2")


def test_a_node_unrouted_and_its_disks_broken_give_the_same_shapes():
    # EC12P4 on 8 nodes x 2 disks: node 0 holds units 0 and 1
    by_node = cluster(16, lambda i: i // 2, routed=range(1, 8))
    by_disk = cluster(16, lambda i: i // 2, routed=range(8), broken=(0, 1))
    a = by_node.warm_decode([token(EC12P4)], 3)
    b = by_disk.warm_decode([token(EC12P4)], 3)
    assert a == b == {"shapes": ["12+4/want2/524288"], "counts": 3, "missed": {}}
    assert by_node.cluster.codec.calls == by_disk.cluster.codec.calls
    # one disk of a routed node: one row wanted, and the broken unit is not read from
    one = cluster(16, lambda i: i // 2, routed=range(8), broken=(1,))
    assert one.warm_decode([token(EC12P4)], 2)["shapes"] == ["12+4/want1/524288"]
    healthy = cluster(16, lambda i: i // 2, routed=range(8))
    assert healthy.warm_decode([token(EC12P4)], 2) == {"shapes": [], "counts": 2, "missed": {}}
    assert healthy.cluster.codec.calls == []


def test_named_shapes_reach_the_service_at_exactly_the_counts_asked():
    dep = cluster(16, lambda i: i // 2, routed=range(1, 8))
    named = [{"n": 18, "m": 1, "rows": 1, "shard_bytes": 262144, "max_count": 3}]
    got = dep.warm_decode([token(EC12P4)], 2, named)
    assert got == {"shapes": ["12+4/want2/524288", "18+1/want1/262144"], "counts": 2, "missed": {},
                   "named_counts": {"18+1/want1/262144": 3}}
    calls = dep.cluster.codec.calls
    # the inferred shape at batch counts 1, 2; the named one at 1, 2, 3: b jobs while max_batch is b
    assert [c[0] for c in calls if c[2] == 12] == [1, 2, 2]
    assert [c for c in calls if c[2] == 18] == [(b, "decode", 18, 1, 1, 262144) for b in (1, 2, 3) for _ in range(b)]
    assert (dep.cluster.codec.max_batch, dep.cluster.codec.max_wait) == (32, 0.0)  # restored
    # named shapes alone (a cell whose GETs decode nothing: warm.decode false)
    alone = cluster(16, lambda i: i // 2, routed=range(8))
    assert alone.warm_decode([], 24, named)["shapes"] == ["18+1/want1/262144"]
    assert len(alone.cluster.codec.calls) == 1 + 2 + 3


def test_named_encode_shapes_go_through_encode():
    dep = cluster(16, lambda i: i // 2, routed=range(8))
    dep.cluster.access = SimpleNamespace(policies=[], max_blob_size=4 << 20)
    got = dep.warm_encode([], 8, [{"n": 18, "m": 1, "shard_bytes": 262144, "max_count": 2}])
    assert got == {"shapes": ["18+1/262144"], "counts": 8, "missed": {}, "named_counts": {"18+1/262144": 2}}
    assert dep.cluster.codec.calls == [(b, "encode", 18, 1, 18, 262144) for b in (1, 2) for _ in range(b)]


@pytest.mark.parametrize("asked,cap", [(40, 32), (3, 3)])
def test_a_named_count_is_capped_by_the_services_max_batch(asked, cap):
    dep = cluster(16, lambda i: i // 2, routed=range(8))
    got = dep.warm_decode([], 1, [{"n": 12, "m": 4, "rows": 1, "shard_bytes": 5000, "max_count": asked}])
    assert got["named_counts"] == {"12+4/want1/16384": cap}
    assert len(dep.cluster.codec.calls) == cap * (cap + 1) // 2
