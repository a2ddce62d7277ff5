"""closed_get's load and loop (its streams, its seeded-uniform choice, its
compare of every body) with the operator's declaration in front: at `start`,
before the first GET, the disks of the nodes in `declare_broken_nodes` are
read from GET /admin/disks and declared broken, one POST /admin/disk/set each
(upstream's clustermgr /disk/set). From that call on the scheduler rebuilds
what those disks held, under the readers.

A program without the call ends the generator in prepare(), inside set-up and
before any data is loaded: the probe below posts a disk id that cannot exist,
and only the handler itself answers 404 "unknown disk". Any answer but success
to a real declaration ends the generator before any GET, so a window without a
rebuild prints no result. Parameters: closed_get's, and declare_broken_nodes."""

from __future__ import annotations

import json

import closed_get
import wire
from genlib import sleep_until

NO_SUCH_DISK = -1


def _call(client: wire.Client, method: str, path: str):
    status, body = client._request(method, path, None)
    try:
        return status, json.loads(body.decode() or "null")
    except ValueError:
        return status, body[:200].decode(errors="replace")


def declare_broken(addr: str, nodes: list[int]) -> list[dict]:
    """Declare every disk of ``nodes`` broken; the answers of the calls."""
    c = wire.Client(addr)
    try:
        status, disks = _call(c, "GET", "/admin/disks")
        if status != 200:
            raise SystemExit(f"GET /admin/disks -> {status} {disks}")
        mine = [d["disk_id"] for d in disks if d["node_id"] in nodes]
        if not mine:
            raise SystemExit(f"no disk of nodes {nodes} in /admin/disks")
        out = []
        for disk_id in mine:
            status, answer = _call(c, "POST", f"/admin/disk/set?disk_id={disk_id}&status=broken")
            if status != 200 or answer.get("status") != "broken":
                raise SystemExit(f"POST /admin/disk/set disk {disk_id} -> {status} {answer}")
            out.append(answer)
        return out
    finally:
        c.close()


class Generator(closed_get.Generator):
    def prepare(self) -> None:
        c = wire.Client(self.spec["addr"])
        status, answer = _call(c, "POST", f"/admin/disk/set?disk_id={NO_SUCH_DISK}&status=broken")
        c.close()
        if status != 404 or "unknown disk" not in str(answer):
            raise SystemExit(f"the program has no POST /admin/disk/set (the probe got {status} {answer}): "
                             "this cell cannot run on it")
        super().prepare()

    def run(self, start: float, t0: float, t1: float) -> dict:
        sleep_until(start)
        declared = declare_broken(self.spec["addr"], self.p["declare_broken_nodes"])
        result = super().run(start, t0, t1)
        result["declared"] = declared
        return result
