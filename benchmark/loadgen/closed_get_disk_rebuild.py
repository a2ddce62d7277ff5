"""closed_get's load and loop (its streams, its seeded-uniform choice, its
compare of every body) around the operator's declaration of single DISKS on
nodes that stay up: `declare_broken_disks` names each as {"node", "nth"}, the
nth disk of that node in GET /admin/disks order.

load(): closed_get's load on the healthy cluster; then the disk_repair switch
is held (POST /admin/switch?name=disk_repair&enabled=0) and each disk is
declared broken (POST /admin/disk/set, upstream's clustermgr /disk/set), so
that the harness's decode warm-up, which comes after the load, sees the damage
the readers will meet while nothing is rebuilt yet. run(): at `start`, before
the first GET, the switch is released (enabled=1): from that call on the
scheduler rebuilds what the disks held, under the readers.

A program that cannot run the cell ends the generator in prepare(), inside
set-up and before any data is loaded, by two probes that touch no real disk:
closed_get_rebuild's (a disk id that cannot exist: only the handler itself
answers 404 "unknown disk") and one for the rebuild by local stripe (GET
/metrics renders cfs_scheduler_rebuild_local_jobs from the scheduler's start,
at 0, on a program that has it). Any answer but success to the switch or to a
declaration ends the generator before any GET, so a window without a rebuild
prints no result. Parameters: closed_get's, and declare_broken_disks."""

from __future__ import annotations

import json

import closed_get
import wire
from genlib import sleep_until

NO_SUCH_DISK = -1
SWITCH = "disk_repair"
LOCAL_REBUILD_SERIES = b"cfs_scheduler_rebuild_local_jobs"


def _call(client: wire.Client, method: str, path: str):
    status, body = client._request(method, path, None)
    try:
        return status, json.loads(body.decode() or "null")
    except ValueError:
        return status, body[:200].decode(errors="replace")


def set_switch(client: wire.Client, enabled: bool) -> None:
    status, answer = _call(client, "POST", f"/admin/switch?name={SWITCH}&enabled={int(enabled)}")
    if status != 200 or answer != {SWITCH: enabled}:
        raise SystemExit(f"POST /admin/switch {SWITCH} enabled={int(enabled)} -> {status} {answer}")


def declare_broken(client: wire.Client, wanted: list[dict]) -> list[dict]:
    """Declare the nth disk of each named node broken; the answers of the calls."""
    status, disks = _call(client, "GET", "/admin/disks")
    if status != 200:
        raise SystemExit(f"GET /admin/disks -> {status} {disks}")
    out = []
    for w in wanted:
        mine = [d["disk_id"] for d in disks if d["node_id"] == w["node"]]
        if w["nth"] >= len(mine):
            raise SystemExit(f"node {w['node']} has {len(mine)} disks in /admin/disks, no disk {w['nth']}")
        disk_id = mine[w["nth"]]
        status, answer = _call(client, "POST", f"/admin/disk/set?disk_id={disk_id}&status=broken")
        if status != 200 or answer.get("status") != "broken":
            raise SystemExit(f"POST /admin/disk/set disk {disk_id} -> {status} {answer}")
        out.append(answer)
    return out


class Generator(closed_get.Generator):
    def prepare(self) -> None:
        c = wire.Client(self.spec["addr"])
        try:
            status, answer = _call(c, "POST", f"/admin/disk/set?disk_id={NO_SUCH_DISK}&status=broken")
            if status != 404 or "unknown disk" not in str(answer):
                raise SystemExit(f"the program has no POST /admin/disk/set (the probe got {status} {answer}): "
                                 "this cell cannot run on it")
            status, text = c._request("GET", "/metrics", None)
            if status != 200 or LOCAL_REBUILD_SERIES not in text:
                raise SystemExit(f"the program renders no {LOCAL_REBUILD_SERIES.decode()} (GET /metrics -> {status}): "
                                 "it does not rebuild a disk by its AZ's local stripe, this cell cannot run on it")
        finally:
            c.close()
        super().prepare()

    def load(self) -> dict:
        loaded = super().load()
        if not loaded["failed"]:
            c = wire.Client(self.spec["addr"])
            try:
                set_switch(c, False)
                self.declared = declare_broken(c, self.p["declare_broken_disks"])
            finally:
                c.close()
        return loaded

    def run(self, start: float, t0: float, t1: float) -> dict:
        c = wire.Client(self.spec["addr"])
        sleep_until(start)
        try:
            set_switch(c, True)
        finally:
            c.close()
        result = super().run(start, t0, t1)
        result["declared"] = self.declared
        return result
