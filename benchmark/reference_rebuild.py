"""The plain reference for a disk rebuild: the row a lost stripe position held,
from the shards that survive, and a dict-of-bytes model of "lose a node, rebuild
each unit onto a disk that holds none of its volume" that states the placement
guarantees. Numpy only, on reference.py's field and generator and
reference_decode.py's solve; nothing of the program.

A global position (data or parity) is decoded from N global survivors: the
inverse of their generator rows gives the data, the lost position's generator
row gives the shard. A local parity (LRC, positions N + M and up) is the
second-stage Cauchy code of its AZ's data + global-parity rows, so it is
re-encoded from them, those that are lost too being decoded first."""

from __future__ import annotations

import numpy as np

import reference
import reference_decode

NORMAL, BROKEN, DROPPED = "normal", "broken", "dropped"


def rebuilt_row(shards: list, lost: int, mode: dict, code: dict) -> bytes:
    """The bytes of stripe position ``lost``. ``shards`` holds one entry a
    stripe position, None where nothing can be read; what it holds at ``lost``
    is not looked at."""
    n, m = mode["N"], mode["M"]
    present = [p for p, s in enumerate(shards[: n + m]) if s is not None and p != lost][:n]

    def solve(want: list[int]) -> np.ndarray:
        if len(present) < n:
            raise ValueError(f"only {len(present)} global survivors, need {n}")
        survivors = np.stack([np.frombuffer(bytes(shards[p]), np.uint8) for p in present])
        return reference_decode.solve(present, survivors, want, mode, code)

    if lost < n + m:
        return solve([lost])[0].tobytes()
    azs = mode["az_count"]
    local_n, local_m = (n + m) // azs, mode["L"] // azs
    az, j = divmod(lost - (n + m), local_m)
    src = reference.az_shards(mode, az)
    holes = [p for p in src if shards[p] is None]
    rows = {p: np.frombuffer(bytes(shards[p]), np.uint8) for p in src if shards[p] is not None}
    if holes:
        rows.update(zip(holes, solve(holes)))
    poly = int(code["field_poly"], 16)
    local = reference.matmul(reference.cauchy(local_n, local_m, poly),
                             np.stack([rows[p] for p in src]), poly)
    return local[j].tobytes()


def placement_violations(placements: dict[int, list[int]], status: dict[int, str]) -> list[str]:
    """What a cluster's unit map breaks of the placement guarantees:
    ``placements`` is volume -> the disk of each stripe position, ``status``
    disk -> normal | broken | dropped. Every unit lies on a NORMAL disk (so a
    DROPPED disk holds none), and no disk holds two units of one volume."""
    out = []
    for vid, disks in sorted(placements.items()):
        for pos, disk in enumerate(disks):
            if status.get(disk) != NORMAL:
                out.append(f"volume {vid} position {pos} lies on disk {disk}, which is {status.get(disk)}")
        for disk in sorted({d for d in disks if disks.count(d) > 1}):
            out.append(f"volume {vid} has {disks.count(disk)} units on disk {disk}")
    return out


class Store:
    """The cluster as dictionaries: disk -> node and status, volume -> mode and
    the disk of each position, (volume, position, blob) -> shard bytes."""

    def __init__(self, disks: dict[int, int], modes: dict, code: dict):
        self.node_of = dict(disks)
        self.status = {d: NORMAL for d in disks}
        self.modes, self.code = modes, code
        self.volumes: dict[int, tuple[str, list[int]]] = {}
        self.shards: dict[tuple[int, int, int], bytes] = {}

    def add_volume(self, vid: int, mode: str, placement: list[int]) -> None:
        self.volumes[vid] = (mode, list(placement))

    def put(self, vid: int, bid: int, blob: bytes) -> None:
        stripe = reference.encode(blob, self.modes[self.volumes[vid][0]], self.code)
        for pos, row in enumerate(stripe):
            self.shards[(vid, pos, bid)] = row.tobytes()

    def lose_node(self, node: int) -> list[int]:
        """The node's disks are broken and what they held is gone."""
        lost = [d for d, n in self.node_of.items() if n == node]
        for d in lost:
            self.status[d] = BROKEN
        for (vid, pos, bid) in list(self.shards):
            if self.volumes[vid][1][pos] in lost:
                del self.shards[(vid, pos, bid)]
        return lost

    def rebuild(self) -> int:
        """Every unit of every broken disk onto the least-loaded NORMAL disk
        that holds no unit of its volume; a disk is dropped when it holds none.
        Returns the shards rebuilt."""
        rebuilt = 0
        for disk in sorted(d for d, s in self.status.items() if s == BROKEN):
            for vid, (mode, placement) in sorted(self.volumes.items()):
                for pos in [p for p, d in enumerate(placement) if d == disk]:
                    load = {d: 0 for d, s in self.status.items() if s == NORMAL and d not in placement}
                    for _, other in self.volumes.values():
                        for d in other:
                            if d in load:
                                load[d] += 1
                    if not load:
                        raise ValueError(f"no disk left for volume {vid} position {pos}")
                    total = len(placement)
                    for bid in sorted({b for (v, _, b) in self.shards if v == vid}):
                        stripe = [self.shards.get((vid, p, bid)) for p in range(total)]
                        self.shards[(vid, pos, bid)] = rebuilt_row(stripe, pos, self.modes[mode], self.code)
                        rebuilt += 1
                    placement[pos] = min(load, key=lambda d: (load[d], d))
            self.status[disk] = DROPPED
        return rebuilt

    def violations(self) -> list[str]:
        return placement_violations({v: p for v, (_, p) in self.volumes.items()}, self.status)
