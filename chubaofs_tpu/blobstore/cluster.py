"""MiniCluster — an in-process blobstore cluster for tests and local use.

Reference analog: master/mocktest + docker-compose bring-up (SURVEY §4) — the
reference validates multi-node behavior with in-process fakes speaking the real
interfaces. Here every component is the REAL implementation wired directly:
N blobnodes with D disks each, one clustermgr, one proxy, one access gateway,
one scheduler + repair worker, all sharing one CodecService.
"""

from __future__ import annotations

import os

from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.blobstore.access import Access
from chubaofs_tpu.blobstore.blobnode import BlobNode
from chubaofs_tpu.blobstore.clustermgr import ClusterMgr
from chubaofs_tpu.blobstore.proxy import Proxy
from chubaofs_tpu.blobstore.scheduler import Reclaimer, RepairWorker, Scheduler
from chubaofs_tpu.codec.service import CodecService


class MiniCluster:
    def __init__(
        self,
        root: str,
        n_nodes: int = 6,
        disks_per_node: int = 2,
        azs: int = 1,
        persist_cm: bool = True,
        codec: CodecService | None = None,
        cache: "BlobCache | None" = None,
    ):
        """codec: inject a shared/mesh-backed CodecService (e.g. one built
        with a jax Mesh so access PUT/GET and scheduler repair run their
        device math dp/sp-sharded across every chip); default single-device.
        cache: inject a blobstore.cache.BlobCache for the tiered read plane;
        default comes from the environment (CFS_CACHE_MB > 0), so daemon
        deployments and the capacity harness opt in with one knob."""
        from chubaofs_tpu.blobstore.cache import BlobCache

        self.root = root
        self._owns_codec = codec is None  # injected services outlive us
        self.codec = codec or CodecService()
        if cache is None:
            cache = BlobCache.from_env(os.path.join(root, "cache"))
        self.cache = cache
        self.cm = ClusterMgr(os.path.join(root, "cm") if persist_cm else None)
        self.nodes: dict[int, BlobNode] = {}
        for n in range(1, n_nodes + 1):
            roots = [os.path.join(root, f"node{n}", f"disk{d}") for d in range(disks_per_node)]
            node = BlobNode(node_id=n, disk_roots=roots, cm=self.cm)
            self.nodes[n] = node
            az = (n - 1) % azs
            self.cm.register_disks([
                {"disk_id": disk_id, "node_id": n, "az": az}
                for disk_id in node.disks])
        self.proxy = Proxy(self.cm, data_dir=os.path.join(root, "proxy"))
        self.access = Access(self.cm, self.proxy, self.nodes, codec=self.codec,
                             cache=self.cache)
        self.scheduler = Scheduler(self.cm, self.proxy, self.nodes,
                                   codec=self.codec, cache=self.cache)
        self.worker = RepairWorker(self.scheduler, self.nodes, codec=self.codec)
        self.reclaimer = Reclaimer(self.scheduler, self.nodes)

    def background_tick(self) -> dict:
        """What the daemon's ticker runs: one tick of every background loop
        with the tasks HANDED to the repair worker's own thread and the
        deleter and compaction to the reclaim plane's, never waited for. A
        disk rebuild outlasts any tick and a retention policy deletes all day;
        heartbeats and lease reaping do not queue behind either, and neither
        does whoever waits for the lock the tick runs under."""
        return self._tick(wait=False)

    def run_background_once(self) -> dict:
        """One tick driven to quiescence, for in-process callers (tests, the
        soak, tools): the same steps, with the worker's thread joined before
        the hygiene steps, so that when it returns every task the tick made
        has run, the blob_delete topic is empty and every chunk the rule
        picks is compacted."""
        return self._tick(wait=True)

    def _tick(self, wait: bool) -> dict:
        """One tick of every background loop (the 16-ticker scheduleTask analog):
        detection first (heartbeats, heartbeat expiry, lease reaping, the
        budgeted scrub), then the task planes. Repair tasks, the deleter and
        host-local hygiene (compaction) are other threads' work: kicked here,
        joined only by the in-process driver (``wait``)."""
        # heartbeats are per-node daemon work: a dead/closed engine simply
        # stops beating, which IS the signal the expiry below consumes
        for n in list(self.nodes.values()):
            try:
                n.heartbeat(self.cm)
            except Exception:
                pass
        dead_disks = self.scheduler.check_node_health()
        reaped = self.scheduler.reap_expired()
        with trace.stage("scheduler.scrub"):
            scrubbed = self.scheduler.run_scrub()
        with trace.stage("scheduler.inspect"):
            inspected = self.scheduler.inspect_volumes()
        polled = self.scheduler.poll_repair_topic()
        tier_msgs = self.scheduler.run_tier()
        disk_tasks = self.scheduler.check_disks()
        balance_task = self.scheduler.check_balance()
        ran = deleted = compacted = 0
        if wait:
            ran = self.worker.wait_idle()
            compacted0 = self.reclaimer.compacted
            deleted = self.reclaimer.wait_idle()
            compacted = self.reclaimer.compacted - compacted0
        else:
            self.worker.kick()
            self.reclaimer.kick()
        return {
            "inspect_msgs": inspected,
            "repair_msgs": polled,
            "tier_msgs": tier_msgs,
            "disk_tasks": len(disk_tasks),
            "balance_tasks": 1 if balance_task else 0,
            "tasks_ran": ran,
            "deletes": deleted,
            "compacted_bytes": compacted,
            "hb_expired_disks": len(dead_disks),
            "leases_reaped": reaped,
            "scrub_findings": scrubbed,
        }

    def close(self):
        self.worker.close()  # first: a migrate in flight stops between stripes
        self.reclaimer.close()
        if self._owns_codec:  # never kill a shared/injected service
            self.codec.close()
        self.access.close()
        for node in self.nodes.values():
            node.close()
        self.cm.close()
