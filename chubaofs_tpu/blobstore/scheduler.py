"""Scheduler — the background task brain: shard repair, disk repair/drop,
balance, blob delete.

Reference counterpart: blobstore/scheduler (migrate state machines with
prepare/work/finish queues, migrate.go:322-347; Kafka consumers feeding
ShardRepairMgr shard_repairer.go:103 and blob_deleter.go; workers PULL tasks
via HTTPTaskAcquire, service.go:84, repair tasks served first). Shapes kept:

  * tasks move through PREPARED -> WORKING -> FINISHED and survive restarts by
    reloading from the clustermgr-persisted task table;
  * workers acquire tasks (repair before balance) and report completion;
  * the repair math is the degraded GET's primitive, CodecService.decode_rows:
    a disk-repair task covers every (volume, bid) on the dead disk, each
    stripe asks for the ONE row its unit holds, and all stripes of a unit
    share one matrix, so their jobs batch by content on the device
    (SURVEY §3.5's bulk-repair config);
  * tasks run on the worker's own thread (RepairWorker.kick): a rebuild that
    outlasts a background tick never holds the tick;
  * the blob deleter and chunk compaction run on the reclaim plane's own
    thread (Reclaimer), woken by the blob_delete topic itself: a DELETE is
    applied as fast as the topic fills, never once a tick.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field

import numpy as np

from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.blobstore.blobnode import BlobNode, NoSuchShard, ShardDeleted, classify_io_error
from chubaofs_tpu.blobstore.clustermgr import (
    DISK_DROPPED,
    DISK_NORMAL,
    ClusterMgr,
    VolumeInfo,
    make_vuid,
    parse_vuid,
)
from chubaofs_tpu.blobstore.proxy import (
    TOPIC_BLOB_DELETE,
    TOPIC_BLOB_HOT,
    TOPIC_SHARD_REPAIR,
    Proxy,
)
from chubaofs_tpu.codec.service import CodecService, default_service
from chubaofs_tpu.utils.exporter import BATCH_BUCKETS, RATIO_BUCKETS, registry

TASK_PREPARED = "prepared"
TASK_WORKING = "working"
TASK_FINISHED = "finished"
TASK_FAILED = "failed"  # exhausted retries; eligible for re-creation

KIND_SHARD_REPAIR = "shard_repair"
KIND_DISK_REPAIR = "disk_repair"
KIND_DISK_DROP = "disk_drop"
KIND_BALANCE = "balance"
KIND_TIER_PROMOTE = "tier_promote"
KIND_TIER_DEMOTE = "tier_demote"

# acquisition priority (service.go:84: repair first; tier migration is an
# optimization, so it yields to every durability task)
_PRIORITY = [KIND_SHARD_REPAIR, KIND_DISK_REPAIR, KIND_DISK_DROP,
             KIND_BALANCE, KIND_TIER_PROMOTE, KIND_TIER_DEMOTE]

_TASK_STATES = (TASK_PREPARED, TASK_WORKING, TASK_FINISHED, TASK_FAILED)

# cfs_scheduler_delete_blobs{result}: `ok` every unit of the blob took both
# phases (or held nothing of it); `partial` a unit could not be reached (a
# dark node, a disk held BROKEN): the inspector or the rebuild finishes it
DELETE_RESULTS = ("ok", "partial")


def stage_overlap_ratio(stages) -> float | None:
    """Download/decode overlap of one repair span's stages: intersection of
    the 'download' interval union with the codec.* interval union, over the
    SMALLER of the two — 0 means the pipeline degenerated to serial, >0 means
    survivor downloads really ran while the device decoded. None when either
    side never happened (nothing to overlap)."""
    dl = [(off, off + dur) for name, off, dur in stages if name == "download"]
    dec = [(off, off + dur) for name, off, dur in stages
           if name.startswith("codec.")]
    return trace.overlap_ratio(dl, dec)


@dataclass
class Task:
    task_id: str
    kind: str
    state: str = TASK_PREPARED
    vid: int = 0
    bid: int = 0
    bad_idx: list[int] = field(default_factory=list)
    disk_id: int = 0
    dest_disk_id: int | None = None  # None = pick at execution
    size: int = 0  # tier_promote: the blob's true byte length
    created: float = field(default_factory=time.time)
    retries: int = 0
    error: str = ""
    # current lease number (0 = never leased). Monotonic across the
    # scheduler's lifetime; a report carrying an older lease is STALE — the
    # reaper requeued and re-leased the task after that worker went quiet.
    lease: int = 0


class Scheduler:
    """Leader-elected background brain (single leader here; raft wraps later)."""

    def __init__(self, cm: ClusterMgr, proxy: Proxy, nodes: dict[int, BlobNode],
                 codec: CodecService | None = None, record_log=None,
                 cache=None):
        from chubaofs_tpu.blobstore.taskswitch import SwitchMgr

        self.cm = cm
        self.proxy = proxy
        self.nodes = nodes
        self.codec = codec or default_service()
        # the gateway's BlobCache when co-located (MiniCluster): the deleter
        # punches blobs out of it before shards disappear
        self.cache = cache
        # switches persist in the clustermgr config KV (task_switch.go:26);
        # pull persisted state so a restarted scheduler honors prior settings
        self.switches = SwitchMgr(config_get=cm.get_config,
                                  config_set=cm.set_config)
        self.switches.refresh()
        self.record_log = record_log  # common/recordlog: finished-task audit
        self._lock = threading.Lock()
        self._tasks: dict[str, Task] = {}
        self._seq = 0
        self._inspect_cursor = 0  # round-robin position over volume ids
        # leased scheduling (the task_runner.go lease/renewal analog): every
        # acquire hands out a monotonic deadline; the reaper requeues expired
        # WORKING tasks with backoff so a dead worker can never strand one.
        self.lease_ms = float(os.environ.get("CFS_REPAIR_LEASE_MS", "30000"))
        self.requeue_backoff_s = 0.5  # doubled per expiry, capped below
        self.requeue_backoff_cap_s = 30.0
        # expiries before a WORKING task goes terminal FAILED (reap_expired)
        self.max_lease_expiries = 5
        # heartbeat-silence window after which a disk counts as dead (the
        # kill-a-blobnode detection path; generous default so slow test
        # phases never false-positive — the kill soak tightens it)
        self.hb_timeout_s = float(os.environ.get("CFS_HB_TIMEOUT_S", "60"))
        # tier demotion: a promoted blob that produces NO heat signal for
        # this many tier sweeps has gone cold — its replica copy is freed
        # and reads fall back to EC
        self.demote_sweeps = int(os.environ.get("CFS_DEMOTE_SWEEPS", "8"))
        self._tier_idle: dict[tuple[int, int], int] = {}  # under self._lock
        # recently-deleted (vid, bid)s, noted BEFORE the deleter touches
        # tier/cache state: an in-flight promote re-checks this after
        # committing its redirect, closing the promote-vs-delete race in
        # daemon deployments where the two run on different threads.
        # Bounded LRU; entries only need to outlive the concurrency window
        # (a promote for a long-gone blob fails on the punched EC read).
        self._deleted_recent: OrderedDict[tuple[int, int], None] = \
            OrderedDict()  # under self._lock
        self._lease_seq = 0
        self._lease_deadline: dict[str, float] = {}  # task_id -> monotonic
        self._not_before: dict[str, float] = {}      # requeue backoff gate
        self._expiries: dict[str, int] = {}          # per-task expiry count
        self._load_tasks()
        with self._lock:
            self._update_gauges_locked()
        # how an LRC volume's disk was rebuilt: made here, at 0, so that they
        # render before their first increment (a reader of "no byte crossed
        # the AZ boundary" must find a series that says 0, not none)
        for name in ("rebuild_local_jobs", "rebuild_cross_az_bytes",
                     "rebuild_local_fallbacks"):
            registry("scheduler").counter(name)
        # the deleter's, likewise: blobs by how their delete ended, and the
        # blob_delete topic's depth
        for result in DELETE_RESULTS:
            registry("scheduler").counter("delete_blobs", {"result": result})
        registry("scheduler").gauge("delete_backlog").set(proxy.delete_backlog())

    # -- task table (persisted in the clustermgr config KV, the reference's
    # migrate-task tables in clustermgr: migrate.go:346-347) -------------------

    _TASK_PREFIX = "task/"
    _TASK_SEQ_KEY = "task_seq"

    # in-memory history cap: terminal tasks already left the KV
    # (_persist_task) and the recordlog holds the durable audit; keeping a
    # bounded tail serves `task ls` without letting a long outage — where
    # FAILED tasks are re-created per fresh damage report — grow the table,
    # and with it task ids and memory, without bound
    TERMINAL_KEEP = 256

    def _prune_terminal_locked(self) -> None:
        terminal = [t for t in self._tasks.values()
                    if t.state in (TASK_FINISHED, TASK_FAILED)]
        if len(terminal) <= self.TERMINAL_KEEP:
            return
        terminal.sort(key=lambda t: int(t.task_id.lstrip("t") or 0))
        for t in terminal[: len(terminal) - self.TERMINAL_KEEP]:
            del self._tasks[t.task_id]

    def _has_tombstone(self, node_id: int, vuid: int, bid: int) -> bool:
        """Tombstone probe that tolerates dark hosts: an unreachable node
        simply cannot attest a tombstone (the sweep retries next round)."""
        node = self.nodes.get(node_id)
        if node is None:
            return False
        try:
            return bool(node.has_tombstone(vuid, bid))
        except Exception:
            return False

    def _load_tasks(self):
        """Reload open tasks after a restart; WORKING tasks re-queue (their
        worker died with us — the reference's junk-task cleanup re-drives).
        The id counter persists separately so completed tasks' ids are never
        reissued (the recordlog audit keys on them)."""
        self._seq = int(self.cm.get_config(self._TASK_SEQ_KEY) or 0)
        for key, raw in self.cm.config_items(self._TASK_PREFIX):
            if not raw:
                continue
            t = Task(**json.loads(raw))
            if t.state == TASK_WORKING:
                t.state = TASK_PREPARED
            self._tasks[t.task_id] = t
            # lease numbers stay monotonic across reloads so a pre-crash
            # worker's report can never alias a fresh lease
            self._lease_seq = max(self._lease_seq, t.lease)

    def _persist_task(self, t: Task):
        key = self._TASK_PREFIX + t.task_id
        if t.state in (TASK_FINISHED, TASK_FAILED):
            # terminal states LEAVE the table (the recordlog keeps the audit);
            # a real delete, so the config KV never grows with task history
            self.cm.del_config(key)
            return
        self.cm.set_config(key, json.dumps(t.__dict__))

    def _new_task(self, **kw) -> Task:
        with self._lock:
            return self._new_task_locked(**kw)

    def _new_task_locked(self, **kw) -> Task:
        self._seq += 1
        self.cm.set_config(self._TASK_SEQ_KEY, str(self._seq))
        t = Task(task_id=f"t{self._seq}", **kw)
        self._tasks[t.task_id] = t
        self._persist_task(t)
        self._update_gauges_locked()
        return t

    def tasks(self, kind: str | None = None, state: str | None = None) -> list[Task]:
        with self._lock:
            return [
                t
                for t in self._tasks.values()
                if (kind is None or t.kind == kind)
                and (state is None or t.state == state)
            ]

    # -- producers -----------------------------------------------------------

    def poll_repair_topic(self, max_msgs: int = 64) -> int:
        """Drain the shard-repair topic into repair tasks (shard_repairer.go:103).

        Deduped by (vid, bid): every degraded GET emits a message, but one open
        task repairs the whole stripe."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_SHARD_REPAIR

        if not self.switches.enabled(SWITCH_SHARD_REPAIR):
            return 0
        topic = self.proxy.topics[TOPIC_SHARD_REPAIR]
        msgs = topic.consume("scheduler", max_msgs)
        with self._lock:
            # terminal tasks don't block a fresh attempt: a FAILED task means
            # retries ran out under the conditions of the time (e.g. a dark
            # AZ); the damage persisting past that deserves a new task, not
            # permanent abandonment (TASK_FAILED is "eligible for re-creation")
            open_keys = {
                (t.vid, t.bid)
                for t in self._tasks.values()
                if t.kind == KIND_SHARD_REPAIR
                and t.state not in (TASK_FINISHED, TASK_FAILED)
            }
        for m in msgs:
            key = (m["vid"], m["bid"])
            bad = self._still_reported(m)
            if key in open_keys or not bad or self._owned_by_disk_repair(m["vid"], bad):
                continue
            open_keys.add(key)
            self._new_task(
                kind=KIND_SHARD_REPAIR, vid=m["vid"], bid=m["bid"], bad_idx=bad
            )
        topic.commit("scheduler", len(msgs))
        return len(msgs)

    def _still_reported(self, msg: dict) -> list[int]:
        """The reported positions whose unit is still the one the report was
        made against. A unit re-homed since (its epoch moved on) was rebuilt
        whole by the migrate that moved it: the degraded GETs of a rebuild
        report faster than a tick drains the topic, and every report still
        waiting when a unit is re-homed would otherwise become a task that
        gathers a whole stripe to find nothing missing."""
        bad, epochs = msg["bad_idx"], msg.get("epochs")
        if not epochs:
            return bad
        try:
            units = self.cm.get_volume(msg["vid"]).units
            return [i for i, e in zip(bad, epochs) if units[i].epoch == e]
        except Exception:
            return bad  # an unknown volume or index: the task says why

    def _owned_by_disk_repair(self, vid: int, bad_idx: list[int]) -> bool:
        """Every reported position lies on a disk that is not NORMAL: the
        disk-level repair rebuilds and re-homes it (shard_repairer.go leaves a
        broken disk's shards to the disk repairer the same way), so a degraded
        GET's report of it is no task."""
        try:
            units = self.cm.get_volume(vid).units
            return all(self.cm.disk_status(units[i].disk_id) != DISK_NORMAL
                       for i in bad_idx)
        except Exception:
            return False  # an unknown volume or index: the task says why

    def check_disks(self) -> list[Task]:
        """Turn broken disks into disk-repair tasks (disk_repairer analog).

        Destination disks are picked per-volume at execution time so the
        no-two-units-of-a-volume-per-disk invariant holds."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_DISK_REPAIR

        if not self.switches.enabled(SWITCH_DISK_REPAIR):
            return []
        out = []
        # check and creation under one lock: the ticker and the operator's
        # declaration (an HTTP thread) may both find the same disk taskless
        with self._lock:
            for disk in self.cm.broken_disks():
                # an open (prepared/working) task blocks re-creation; a FAILED
                # one does not — the disk is still broken and must be retried
                if any(t.kind == KIND_DISK_REPAIR and t.disk_id == disk.disk_id
                       and t.state in (TASK_PREPARED, TASK_WORKING)
                       for t in self._tasks.values()):
                    continue
                out.append(self._new_task_locked(
                    kind=KIND_DISK_REPAIR, disk_id=disk.disk_id))
        return out

    def inspect_volumes(self, max_volumes: int = 4) -> int:
        """Proactive integrity sweep (scheduler/volume_inspector.go): walk a
        cursor-bounded batch of volumes, verify every stripe position of every
        bid is present AND passes its crc32block framing, and feed anything
        broken to the repair topic — discovery without waiting for a client GET.
        Gated by SWITCH_VOL_INSPECT. Returns repair messages produced."""
        from chubaofs_tpu.blobstore.blobnode import STATUS_MARK_DELETE
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_VOL_INSPECT

        if not self.switches.enabled(SWITCH_VOL_INSPECT):
            return 0
        with self._lock:
            vids = sorted(self.cm.volumes)
            if not vids:
                return 0
            start = self._inspect_cursor % len(vids)
            batch = (vids[start:] + vids[:start])[:max_volumes]
            self._inspect_cursor = (start + len(batch)) % len(vids)
        produced = 0
        for vid in batch:
            vol = self.cm.get_volume(vid)
            t = vol.tactic()
            # bid -> stripe positions holding it, with index status
            seen: dict[int, dict[int, int]] = {}
            for u in vol.units:
                node = self.nodes.get(u.node_id)
                if node is None:
                    continue
                try:
                    metas = node.list_shards(u.vuid)
                except Exception:
                    continue
                for m in metas:
                    seen.setdefault(m.bid, {})[u.index] = m.status
            for bid, have in sorted(seen.items()):
                # a tombstone ANYWHERE means this bid was deleted: finish the
                # partial delete (idempotent, retried every sweep) instead of
                # resurrecting it — checked BEFORE the mark-delete skip so a
                # half-marked straggler can't wedge forever
                tombstoned = any(
                    self._has_tombstone(u.node_id, u.vuid, bid)
                    for u in vol.units
                )
                if tombstoned:
                    for idx in have:
                        unit = vol.units[idx]
                        node = self.nodes.get(unit.node_id)
                        if node is None:
                            continue
                        try:
                            node.delete_shard(unit.vuid, bid)
                        except Exception:
                            pass  # node down: retried on the next sweep
                    continue
                if any(st == STATUS_MARK_DELETE for st in have.values()):
                    continue  # delete in flight; the deleter owns this bid
                bad = []
                for idx in range(t.total):
                    unit = vol.units[idx]
                    node = self.nodes.get(unit.node_id)
                    if node is None or idx not in have:
                        bad.append(idx)
                        continue
                    try:
                        node.get_shard(unit.vuid, bid)  # full CRC-framed read
                    except ShardDeleted:
                        # deleted since this volume was listed (the deleter
                        # runs beside this sweep): not damage, no report
                        bad = []
                        break
                    except Exception:
                        bad.append(idx)
                if bad:
                    self.proxy.send_shard_repair(vid, bid, bad, "inspect")
                    produced += 1
        if produced:
            registry("scheduler").counter("inspect_findings").add(produced)
        return produced

    def drop_disk(self, disk_id: int) -> Task:
        """Manual decommission -> migrate everything off (disk_drop analog)."""
        return self._new_task(kind=KIND_DISK_DROP, disk_id=disk_id)

    def check_balance(self, min_gap: int = 3) -> Task | None:
        """Even out chunk counts (scheduler/balancer.go): when the most-loaded
        normal disk leads the least-loaded same-AZ disk by >= min_gap chunks,
        create ONE balance task moving a single volume unit off it. Gated by
        SWITCH_BALANCE; one rebalance in flight at a time."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_BALANCE

        if not self.switches.enabled(SWITCH_BALANCE):
            return None
        if any(t.state in (TASK_PREPARED, TASK_WORKING)
               for t in self.tasks(KIND_BALANCE)):
            return None
        by_az: dict[int, list] = {}
        for d in self.cm.disks.values():
            if d.status == DISK_NORMAL:
                by_az.setdefault(d.az, []).append(d)
        # balance is intrinsically per-AZ (moves never cross AZs): evaluate
        # every AZ's own spread, not one global maximum
        for az, disks in sorted(by_az.items()):
            if len(disks) < 2:
                continue
            src = max(disks, key=lambda d: d.chunk_count)
            low = min(d.chunk_count for d in disks if d.disk_id != src.disk_id)
            if src.chunk_count - low < min_gap:
                continue
            for vol, unit in self.cm.volumes_on_disk(src.disk_id):
                try:
                    dest = self.pick_dest_disk(
                        exclude={u.disk_id for u in vol.units}, az=az)
                except RuntimeError:
                    continue
                # the move must CONVERGE: a destination nearly as loaded as
                # the source would just ping-pong units back and forth
                if self.cm.disks[dest].chunk_count + min_gap > src.chunk_count:
                    continue
                registry("scheduler").counter("balance_tasks").add()
                return self._new_task(kind=KIND_BALANCE, vid=vol.vid,
                                      disk_id=src.disk_id,
                                      dest_disk_id=dest)
        return None

    def pick_dest_disk(self, exclude: set[int], az: int) -> int:
        """Least-loaded normal disk in the AZ, outside the exclusion set
        (source disk + every disk already hosting a unit of the volume)."""
        candidates = [
            d
            for d in self.cm.disks.values()
            if d.status == DISK_NORMAL and d.disk_id not in exclude and d.az == az
        ]
        if not candidates:
            raise RuntimeError(f"no destination disk available in AZ {az}")
        return min(candidates, key=lambda d: d.chunk_count).disk_id

    # -- worker pull API (HTTPTaskAcquire analog) -----------------------------

    def acquire_task(self) -> Task | None:
        """Hand out the highest-priority PREPARED task under a LEASE: the
        returned task carries a fresh lease number and a monotonic deadline;
        a worker that never reports is reaped by reap_expired() and the task
        requeues with backoff. Capture task.lease IMMEDIATELY — the shared
        Task object's lease advances if the task is ever re-leased."""
        now = time.monotonic()
        got: Task | None = None
        with self._lock:
            for kind in _PRIORITY:
                if got is not None:
                    break
                for t in self._tasks.values():
                    if t.kind != kind or t.state != TASK_PREPARED:
                        continue
                    if self._not_before.get(t.task_id, 0.0) > now:
                        continue  # requeue backoff still cooling
                    t.state = TASK_WORKING
                    self._lease_seq += 1
                    t.lease = self._lease_seq
                    # persisted not for the WORKING state (reload demotes it
                    # back to PREPARED regardless) but for the LEASE number:
                    # _load_tasks restores _lease_seq from the stored maximum,
                    # so a worker that outlives a scheduler crash can never
                    # find its old lease number reissued to someone else
                    self._persist_task(t)
                    self._lease_deadline[t.task_id] = \
                        now + self.lease_ms / 1e3
                    self._update_gauges_locked()
                    got = t
                    # emit UNDER the lock: the lock serializes every lease
                    # transition, so stamping here keeps the timeline's
                    # order identical to the state machine's (an expiry's
                    # event can never trail its re-acquisition's), and the
                    # mutable lease field is captured before it can advance
                    from chubaofs_tpu.utils import events

                    events.emit("lease_acquired", entity=t.task_id,
                                detail={"kind": t.kind, "lease": t.lease,
                                        "disk_id": t.disk_id, "vid": t.vid,
                                        "bid": t.bid})
                    break
        return got

    def reap_expired(self) -> int:
        """Requeue WORKING tasks whose lease deadline passed (the junk-task
        cleanup loop the reference runs against dead workers): state back to
        PREPARED behind an exponential requeue backoff, counted by
        cfs_scheduler_lease_expired. The late worker's eventual report is
        dropped as stale (its lease no longer matches). A task that expires
        max_lease_expiries times goes terminal FAILED instead — workers
        renew mid-task (renew_lease), so repeated expiry means every
        execution dies, and re-executing forever is not an error path."""
        from chubaofs_tpu.utils import events

        now = time.monotonic()
        reaped = 0
        failed = 0
        with self._lock:
            for t in self._tasks.values():
                if t.state != TASK_WORKING:
                    continue
                deadline = self._lease_deadline.get(t.task_id)
                if deadline is not None and now < deadline:
                    continue
                self._lease_deadline.pop(t.task_id, None)
                n = self._expiries.get(t.task_id, 0) + 1
                self._expiries[t.task_id] = n
                if n >= self.max_lease_expiries:
                    t.state = TASK_FAILED
                    t.error = f"lease expired {n}x with no report"
                    self._persist_task(t)
                    self._not_before.pop(t.task_id, None)
                    self._expiries.pop(t.task_id, None)
                    failed += 1
                else:
                    t.state = TASK_PREPARED
                    self._not_before[t.task_id] = now + min(
                        self.requeue_backoff_cap_s,
                        self.requeue_backoff_s * (2 ** (n - 1)))
                reaped += 1
                # emit UNDER the lock (same rationale as acquire_task's):
                # the expiry's timeline stamp must precede any
                # re-acquisition's, and only the lock guarantees that
                terminal = t.state == TASK_FAILED
                events.emit("lease_expired", events.SEV_WARNING,
                            entity=t.task_id,
                            detail={"kind": t.kind, "expiries": n,
                                    "terminal": terminal})
                if terminal:
                    events.emit("task_failed", events.SEV_CRITICAL,
                                entity=t.task_id,
                                detail={"kind": t.kind, "error": t.error})
            if failed:
                self._prune_terminal_locked()
            if reaped:
                self._update_gauges_locked()
        if reaped:
            registry("scheduler").counter("lease_expired").add(reaped)
        if failed:
            registry("scheduler").counter("lease_expired_failed").add(failed)
        return reaped

    def renew_lease(self, task_id: str, lease: int) -> bool:
        """Extend a WORKING task's lease deadline by a full lease_ms (the
        reference task runner's renewal tick). A long disk migrate renews
        between units so a healthy slow worker never loses a race against
        the reaper; False means the lease is gone (task pruned, reaped, or
        re-leased) and the caller must abandon the task."""
        with self._lock:
            t = self._tasks.get(task_id)
            if t is None or t.state != TASK_WORKING or t.lease != lease:
                return False
            self._lease_deadline[task_id] = \
                time.monotonic() + self.lease_ms / 1e3
        registry("scheduler").counter("lease_renewed").add()
        return True

    def report_task(self, task_id: str, ok: bool, error: str = "",
                    lease: int | None = None) -> bool:
        """Worker completion report. Tolerant by contract: an unknown id
        (terminal-task pruning, scheduler reload), a task no longer WORKING
        (the reaper requeued it), or a mismatched lease (it was re-leased to
        another worker) is DROPPED with cfs_scheduler_stale_report — never a
        crash in the worker thread, and never a double state transition.
        Returns True when the report was accepted."""
        with self._lock:
            t = self._tasks.get(task_id)
            stale = (t is None or t.state != TASK_WORKING
                     or (lease is not None and lease != t.lease))
            if stale:
                reason = ("pruned" if t is None else
                          "not_working" if t.state != TASK_WORKING
                          else "lease")
            else:
                self._lease_deadline.pop(task_id, None)
                if ok:
                    t.state = TASK_FINISHED
                else:
                    t.retries += 1
                    t.error = error
                    t.state = TASK_PREPARED if t.retries < 3 else TASK_FAILED
                self._persist_task(t)
                if t.state in (TASK_FINISHED, TASK_FAILED):
                    self._prune_terminal_locked()
                    self._not_before.pop(task_id, None)
                    self._expiries.pop(task_id, None)
                self._update_gauges_locked()
            record = None
            if not stale and self.record_log is not None \
                    and t.state in (TASK_FINISHED, TASK_FAILED):
                record = {
                    "task_id": t.task_id, "kind": t.kind, "state": t.state,
                    "vid": t.vid, "bid": t.bid, "disk_id": t.disk_id,
                    "retries": t.retries, "error": t.error,
                }
        if stale:
            registry("scheduler").counter(
                "stale_report", {"reason": reason}).add()
            return False
        # record outside the lock; the audit trail must never alter task state
        if record is not None:
            try:
                self.record_log.encode(record)
            except OSError:
                pass
        if t.state in (TASK_FINISHED, TASK_FAILED):
            # terminal transition -> timeline. Emitted from the WORKER'S
            # calling context, so a live repair span's trace id rides along
            # and `cfs-events --correlate <trace>` joins the rebuild-finished
            # event to its repair trace
            from chubaofs_tpu.utils import events

            if t.state == TASK_FINISHED:
                events.emit("task_finished", entity=t.task_id,
                            detail={"kind": t.kind, "vid": t.vid,
                                    "bid": t.bid, "disk_id": t.disk_id,
                                    "retries": t.retries})
            else:
                events.emit("task_failed", events.SEV_CRITICAL,
                            entity=t.task_id,
                            detail={"kind": t.kind, "vid": t.vid,
                                    "bid": t.bid, "disk_id": t.disk_id,
                                    "retries": t.retries, "error": t.error})
        return True

    def _update_gauges_locked(self) -> None:
        """cfs_scheduler_tasks{kind,state} gauges over the (bounded) table —
        the cfs-stat repair rollup's task inventory."""
        counts: dict[tuple[str, str], int] = {}
        for t in self._tasks.values():
            counts[(t.kind, t.state)] = counts.get((t.kind, t.state), 0) + 1
        reg = registry("scheduler")
        for kind in _PRIORITY:
            for state in _TASK_STATES:
                reg.gauge("tasks", {"kind": kind, "state": state}).set(
                    counts.get((kind, state), 0))

    # -- detection drivers (scrub + heartbeat expiry) -------------------------

    def run_scrub(self, max_shards: int = 256) -> int:
        """One budgeted scrub tick across every reachable blobnode: each
        node re-reads up to max_shards live shards through its crc32block
        framing (cursor-resumable, CFS_SCRUB_RATE-limited — see
        BlobNode.scrub_once) and every CRC failure feeds the repair topic.
        This is the datainspect.go half of detection: it finds bitrot
        without waiting for a client GET or a full inspector sweep."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_VOL_INSPECT

        if not self.switches.enabled(SWITCH_VOL_INSPECT):
            return 0
        produced = 0
        for node in list(self.nodes.values()):
            try:
                res = node.scrub_once(max_shards=max_shards)
            except Exception:
                continue  # dark/closed engine: its restart resumes the cursor
            for vuid, bid in res["bad"]:
                vid, idx, _ = parse_vuid(vuid)
                try:
                    self.proxy.send_shard_repair(vid, bid, [idx], "scrub")
                    produced += 1
                except Exception:
                    pass  # proxy down: the next sweep re-finds it
        if produced:
            registry("scheduler").counter("scrub_findings").add(produced)
        return produced

    def check_node_health(self, timeout_s: float | None = None) -> list[int]:
        """Mark disks whose heartbeats went silent as BROKEN (the
        kill-a-blobnode detection path): a dead engine stops heartbeating,
        its disks expire, and check_disks turns them into disk-repair tasks.
        Returns the disk ids newly marked broken."""
        timeout = self.hb_timeout_s if timeout_s is None else timeout_s
        if timeout <= 0:
            return []
        stale = self.cm.expire_heartbeats(timeout)
        if stale:
            registry("scheduler").counter("hb_expired_disks").add(len(stale))
        return stale

    # -- blob deleter ---------------------------------------------------------

    def run_deleter(self, max_msgs: int = 512, pool=None) -> int:
        """One drain of the blob_delete topic (blob_deleter.go's two phases):
        up to ``max_msgs`` messages are grouped by the unit that holds them,
        then EVERY unit mark-deletes its batch of bids (one take of the chunk
        lock, one metadb batch), and only then every unit deletes it (punch
        out, tombstones): mark on every unit before the first punch, as
        upstream. The units run side by side on ``pool`` (the Reclaimer's;
        None: on the caller's thread). Returns the messages consumed."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_BLOB_DELETE

        reg = registry("scheduler")
        if not self.switches.enabled(SWITCH_BLOB_DELETE):
            reg.gauge("delete_backlog").set(self.proxy.delete_backlog())
            return 0
        topic = self.proxy.topics[TOPIC_BLOB_DELETE]
        msgs = topic.consume("deleter", max_msgs)
        if not msgs:
            reg.gauge("delete_backlog").set(0)
            return 0
        with trace.stage("deleter.batch"):
            # a deleted blob leaves EVERY tier. Order matters on a daemon,
            # where GETs serve CONCURRENTLY with this loop: (1) note the
            # delete so an in-flight tier promote re-checks it, (2) drop
            # the hot replica copy, (3) punch the EC shards, (4) invalidate
            # the cache LAST — an invalidate-before-punch would let a GET
            # in the gap refill the cache from the still-readable shards
            # under the post-bump version, and nothing would ever evict
            # those bytes again (the gateway's own delete() already did the
            # pre-delete write-through invalidation for its clients)
            keys = [(m["vid"], m["bid"]) for m in msgs]
            with self._lock:
                for key in keys:
                    self._deleted_recent[key] = None
                while len(self._deleted_recent) > 4096:
                    self._deleted_recent.popitem(last=False)
            by_unit: dict[tuple[int, int], list[int]] = {}  # (node, vuid) -> bids
            units_of: dict[int, list] = {}
            for vid, bid in keys:
                self._drop_hot_copy(vid, bid)
                if vid not in units_of:
                    try:
                        units_of[vid] = [(u.node_id, u.vuid) for u in self.cm.get_volume(vid).units]
                    except Exception:
                        units_of[vid] = []  # an unknown volume holds nothing
                for unit in units_of[vid]:
                    by_unit.setdefault(unit, []).append(bid)
            missed = self._each_unit(by_unit, "mark_delete_shards", pool)
            missed |= self._each_unit(by_unit, "delete_shards", pool)
            done = time.time()
            for m, (vid, bid) in zip(msgs, keys):
                if self.cache is not None:
                    self.cache.invalidate(vid, bid)
                partial = any(u in missed for u in units_of[vid])
                reg.counter("delete_blobs", {"result": DELETE_RESULTS[partial]}).add()
                if "ts" in m and not partial:
                    # the DELETE's acknowledgement -> its last unit punched
                    lag = max(0.0, done - m["ts"])
                    trace.observe_stage("deleter.apply", time.perf_counter() - lag, lag)
            topic.commit("deleter", len(msgs))
        reg.gauge("delete_backlog").set(topic.lag("deleter"))
        return len(msgs)

    def _each_unit(self, by_unit: dict, phase: str, pool) -> set:
        """One phase of a drain on every unit's batch; -> the units that could
        not take it (no such node, a disk the cluster manager holds BROKEN,
        an I/O error: the repair plane owns what they keep)."""
        def one(unit, bids):
            node = self.nodes.get(unit[0])
            if node is None:
                return unit
            try:
                getattr(node, phase)(unit[1], bids)
            except NoSuchShard:
                return None  # the unit has no chunk: it holds nothing of them
            except Exception:
                return unit
            return None

        if pool is None:
            out = [one(u, b) for u, b in by_unit.items()]
        else:
            out = [f.result() for f in [pool.submit(one, u, b) for u, b in by_unit.items()]]
        return {u for u in out if u is not None}

    def _recently_deleted(self, vid: int, bid: int) -> bool:
        with self._lock:
            return (vid, bid) in self._deleted_recent

    # -- tier migration (the cache plane's promoter/demoter, ISSUE 12) --------

    def run_tier(self, max_msgs: int = 64) -> int:
        """One tier sweep: drain the hot-blob topic into promote tasks for
        blobs not yet resident in the hot engine, and create demote tasks
        for promoted blobs whose heat signal has been silent for
        demote_sweeps consecutive sweeps. Worker execution rides the same
        lease machinery as repair (acquire -> lease -> report)."""
        from chubaofs_tpu.blobstore.taskswitch import SWITCH_TIER_MIGRATE

        topic = self.proxy.topics[TOPIC_BLOB_HOT]
        # drain the topic FULLY: the idle-demote counter below reads "no
        # signal this sweep" as cooling, so a partial batch under signal
        # backlog would demote genuinely hot blobs whose messages merely
        # sat past the batch boundary (then re-promote them — churn)
        msgs: list[dict] = []
        while True:
            batch = topic.consume("tier", max_msgs)
            if not batch:
                break
            topic.commit("tier", len(batch))
            msgs.extend(batch)
        if not self.switches.enabled(SWITCH_TIER_MIGRATE):
            # consumed-and-DISCARDED: heat signals are advisory, and the
            # access layer keeps producing them while a cache is armed —
            # leaving them unconsumed would grow hot.jsonl without bound
            # and dump an hours-stale backlog on the sweep that re-enables
            return 0
        hot_now = {(m["vid"], m["bid"]): m.get("size", 0) for m in msgs}
        promoted = self.cm.hot_blobs()
        with self._lock:
            open_keys = {
                (t.vid, t.bid)
                for t in self._tasks.values()
                if t.kind in (KIND_TIER_PROMOTE, KIND_TIER_DEMOTE)
                and t.state not in (TASK_FINISHED, TASK_FAILED)
            }
        for (vid, bid), size in sorted(hot_now.items()):
            if (vid, bid) in promoted or (vid, bid) in open_keys:
                continue
            open_keys.add((vid, bid))
            self._new_task(kind=KIND_TIER_PROMOTE, vid=vid, bid=bid, size=size)
        demote: list[tuple[int, int]] = []
        with self._lock:
            # drop idle entries for blobs no longer promoted (demoted or
            # deleted behind our back) so the table tracks the tier map
            for key in [k for k in self._tier_idle if k not in promoted]:
                del self._tier_idle[key]
            for key in promoted:
                if key in hot_now:
                    self._tier_idle[key] = 0
                    continue
                n = self._tier_idle.get(key, 0) + 1
                self._tier_idle[key] = n
                if n >= self.demote_sweeps and key not in open_keys:
                    demote.append(key)
                    del self._tier_idle[key]
        for vid, bid in demote:
            self._new_task(kind=KIND_TIER_DEMOTE, vid=vid, bid=bid)
        return len(msgs)

    def _drop_hot_copy(self, vid: int, bid: int) -> None:
        """Demote-and-free: drop the tier-map redirect FIRST (readers fall
        back to the authoritative EC copy), then best-effort delete the
        replica shards — an unreachable hot node leaks bytes until its
        chunk is re-imaged, never correctness."""
        if self.cm.hot_location(vid, bid) is None:
            # the common case (never promoted): skip the demote apply —
            # it would mint a durable no-op WAL record per blob delete.
            # Race-safe vs an in-flight promote: the deleter notes the key
            # in _deleted_recent BEFORE calling here, and _tier_promote
            # re-checks that note after committing its redirect
            return
        hot = self.cm.demote_blob(vid, bid)
        if hot is None:
            return
        hot_vid, hot_bid = hot
        from chubaofs_tpu.utils import events

        events.emit("tier_demote", entity=f"blob({vid},{bid})",
                    detail={"vid": vid, "bid": bid, "hot_vid": hot_vid,
                            "hot_bid": hot_bid})
        try:
            vol = self.cm.get_volume(hot_vid)
        except Exception:
            return
        for unit in vol.units:
            node = self.nodes.get(unit.node_id)
            if node is None:
                continue
            try:
                node.mark_delete_shard(unit.vuid, hot_bid)
                node.delete_shard(unit.vuid, hot_bid)
            except Exception:
                pass
        registry("cache").counter("demotes").add()


class _WorkerStopping(Exception):
    """close() reached a migrate in flight: unwind without a report."""


class _PendingRow:
    """A unit's row still in the codec: the job's future and how to cut the
    row's bytes out of its result. The cut runs on the thread that lands the
    row, never in a callback on the codec's dispatcher."""

    __slots__ = ("fut", "cut")

    def __init__(self, fut: Future, cut):
        self.fut, self.cut = fut, cut

    def done(self) -> bool:
        return self.fut.done()

    def result(self) -> bytes:
        return self.cut(self.fut.result())


class _OwnThread:
    """A background plane's own thread: kick() wakes it, it calls _drain()
    until a drain that began after the newest kick has ended, wait_idle() is
    the in-process driver's join. Started by the first kick; close() stops it."""

    thread_name = "worker"

    def __init__(self):
        self._stop = threading.Event()
        self._state = threading.Condition()
        self._thread: threading.Thread | None = None
        self._kicks = 0  # drains asked for
        self._served = 0  # the newest kick a finished drain had seen
        self._ran = 0  # what the drains have counted (tasks, messages)

    def _drain(self) -> None:
        """One drain; calls _count() for what wait_idle() reports."""
        raise NotImplementedError

    def _count(self, n: int = 1) -> None:
        with self._state:
            self._ran += n

    def kick(self) -> None:
        """Wake the thread: it drains until nothing is left."""
        with self._state:
            self._kicks += 1
            if self._thread is None and not self._stop.is_set():
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name=self.thread_name)
                self._thread.start()
            self._state.notify_all()

    def wait_idle(self) -> int:
        """kick(), then wait until a drain that began after this call has
        ended; returns what the drains counted meanwhile. The in-process
        driver's join (MiniCluster.run_background_once): the daemon's tick
        never calls it."""
        with self._state:
            ran0 = self._ran
        self.kick()
        with self._state:
            want = self._kicks
            while self._served < want and not self._stop.is_set():
                self._state.wait(0.5)
            return self._ran - ran0

    def _loop(self) -> None:
        while True:
            with self._state:
                while self._served == self._kicks and not self._stop.is_set():
                    self._state.wait()
                if self._stop.is_set():
                    return
                seen = self._kicks
            try:
                self._drain()
            except Exception:
                # a drain records its own failures; what reaches here is the
                # plane's own (a cm closing under a reload)
                registry("scheduler").counter("worker_loop_errors").add()
            with self._state:
                self._served = seen
                self._state.notify_all()

    def _join(self) -> None:
        self._stop.set()
        with self._state:
            self._state.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout=5)


class RepairWorker(_OwnThread):
    """Executes repair/migrate tasks with batched TPU reconstructs.

    Reference: blobnode's embedded worker (task_runner.go:171,
    work_shard_recover.go:399-547). The TPU-native differences: one task's
    stripes are stacked into large (B, n, k) reconstruct batches instead of
    per-stripe loops, and bulk migrates run a WINDOWED pipeline — up to
    CFS_REPAIR_WINDOW stripes' survivor downloads in flight while earlier
    stripes decode on the device (the PUT pipeline's window pattern applied
    to repair-GET). Every task runs under a `scheduler.repair` span whose
    `download` stages and the codec's `codec.stack`/`codec.matmul` stages let
    cfs-trace prove the overlap.

    Tasks run on the worker's OWN thread: kick() wakes it, wait_idle() is the
    in-process driver's join. A disk rebuild outlasts any background tick, so
    no tick (and no lock a tick holds) ever waits for one; close() stops a
    migrate between two stripes and leaves its task retryable.
    """

    # decode jobs one unit keeps in the codec's one FIFO before its thread
    # waits for the oldest: what its own pipeline needs (a job is back within
    # two gathers: ~1.2 in flight at 25 shards/s on the chip) and no more. The
    # dispatcher serves readers and rebuild in arrival order at a fixed ~95
    # jobs/s, so a deeper window is a larger claim on the readers' share
    # whenever the queue is long: at 8 the rebuild doubled its rate and the
    # readers lost a third of theirs for 8-14 s at a time (PERF.md, PR 36)
    DECODE_AHEAD = 2

    def __init__(self, sched: Scheduler, nodes: dict[int, BlobNode],
                 codec: CodecService | None = None,
                 read_deadline: float = 3.0,
                 repair_window: int | None = None):
        self.sched = sched
        self.cm = sched.cm
        self.nodes = nodes
        self.codec = codec or sched.codec
        # every survivor read races this deadline: a wedged blobnode turns
        # into a typed probe_fail{timeout}, never a silent stall
        self.read_deadline = read_deadline
        if repair_window is None:
            repair_window = int(os.environ.get("CFS_REPAIR_WINDOW", "4"))
        self.repair_window = repair_window  # 0/1 = serial gather
        # stripe-level window workers (one per in-flight gather) and the
        # shard-read fan-out pool they share; both bounded so one repair
        # task can't monopolize a host
        self._stripe_pool = ThreadPoolExecutor(
            max_workers=max(1, repair_window or 1),
            thread_name_prefix="repair-stripe")
        self._shard_pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="repair-io")
        # the worker's own thread (started by the first kick): it drains the
        # task table whenever a kick is newer than the last drain it finished
        super().__init__()
        # (task id, lease, monotonic time of its last renewal) of the migrate
        # the calling thread runs, for _keepalive
        self._lease: tuple[str, int, float] | None = None

    def set_repair_window(self, window: int) -> None:
        """Change the stripe window AND resize the pool that realizes it —
        assigning repair_window bare would leave a pool sized for the old
        window silently serializing (or over-parallelizing) the gathers."""
        if window == self.repair_window:
            return
        self.repair_window = window
        old = self._stripe_pool
        self._stripe_pool = ThreadPoolExecutor(
            max_workers=max(1, window or 1),
            thread_name_prefix="repair-stripe")
        old.shutdown(wait=False)

    def close(self) -> None:
        """Stop the worker's thread and shut down its executors (racelint:
        unjoined-thread). A migrate in flight stops between two stripes
        (_keepalive) and reports nothing: its task stays WORKING until the
        reaper or a restart requeues it, and idempotent write-back makes the
        re-execution safe. wait=False mirrors Access.close — a read wedged on
        a dead node must not stall teardown; it fails on its own deadline."""
        self._join()
        self._stripe_pool.shutdown(wait=False)
        self._shard_pool.shutdown(wait=False)

    # -- the worker's own thread ------------------------------------------------

    thread_name = "repair-worker"

    def _drain(self) -> None:
        """Run tasks until none is left (run_once records a task's failure
        on the task)."""
        while not self._stop.is_set() and self.run_once():
            self._count()

    def _keepalive(self) -> None:
        """Between two stripes of a migrate: stop if the worker is closing,
        and renew the task's lease once a third of it has passed (a unit of
        hundreds of stripes outlasts a lease; the per-unit renewal alone would
        hand a healthy migrate to the reaper)."""
        if self._stop.is_set():
            raise _WorkerStopping()
        if self._lease is None:
            return
        task_id, lease, at = self._lease
        now = time.monotonic()
        if now - at >= self.sched.lease_ms / 3e3:
            if not self.sched.renew_lease(task_id, lease):
                raise RuntimeError(f"lease {lease} lost mid-migrate ({task_id})")
            self._lease = (task_id, lease, now)

    def run_once(self) -> bool:
        """Process one task; failures are recorded on the task, never raised —
        one poisoned stripe must not stall the background plane. The whole
        task executes under a root span so repair traces are analyzable, and
        the report carries the ACQUIRE-time lease: if the lease expired and
        the reaper re-queued the task mid-flight, this report is dropped as
        stale (idempotent write-back makes the re-execution safe)."""
        task = self.sched.acquire_task()
        if task is None:
            return False
        lease = task.lease  # capture NOW: the field advances on re-lease
        reg = registry("scheduler")
        with trace.child_of(trace.current_span(), "scheduler.repair") as span:
            span.set_tag("task", task.task_id)
            span.set_tag("kind", task.kind)
            span.set_tag("window", self.repair_window)
            ok, err = True, ""
            try:
                if task.kind == KIND_SHARD_REPAIR:
                    self._repair_shards(task.vid, task.bid, task.bad_idx)
                elif task.kind == KIND_BALANCE:
                    self._balance_unit(task)
                elif task.kind in (KIND_DISK_REPAIR, KIND_DISK_DROP):
                    self._migrate_disk(task, lease)
                elif task.kind == KIND_TIER_PROMOTE:
                    self._tier_promote(task, lease)
                elif task.kind == KIND_TIER_DEMOTE:
                    self.sched._drop_hot_copy(task.vid, task.bid)
            except _WorkerStopping:
                return False  # no report: the task stays leased, retryable
            except Exception as e:
                ok, err = False, f"{type(e).__name__}: {e}"
            ratio = stage_overlap_ratio(span.stages)
            if ratio is not None:
                span.set_tag("overlap_ratio", round(ratio, 3))
                reg.summary("repair_overlap_ratio",
                            buckets=RATIO_BUCKETS).observe(ratio)
            self.sched.report_task(task.task_id, ok, error=err, lease=lease)
        return True

    # -- tier promotion (EC cold copy -> Replica3 hot engine) ------------------

    def _tier_promote(self, task: Task, lease: int | None = None):
        """Copy one sustained-hot blob into the 3-replica hot engine: read
        its data region off the EC stripe (reconstructing around any damage
        — a hot blob deserves promotion even while degraded), trim to the
        blob's true size, encode the systematic RS(1,2) replica stripe, and
        land it on a Replica3 volume before committing the redirect.
        Idempotent: a re-executed task (lease expiry, crash) sees the
        redirect and returns; a half-written replica set is unreachable
        until promote_blob commits, and put_shard punch-and-append makes
        the rewrite safe."""
        from chubaofs_tpu.codec.codemode import CodeMode, get_tactic

        if self.cm.hot_location(task.vid, task.bid) is not None:
            return
        if self.sched._recently_deleted(task.vid, task.bid):
            return  # the blob is going/gone; don't resurrect it hot
        span = trace.current_span()
        vol = self.cm.get_volume(task.vid)
        t = vol.tactic()
        reads = self._probe(vol, task.bid, range(t.N), span=span)
        if len(reads) == t.N:
            payload = b"".join(reads[i] for i in range(t.N))
        else:
            stripe, present, _ = self._gather(vol, t, task.bid, span=span)
            missing = [i for i in range(t.N + t.M) if i not in present]
            if missing:
                stripe = self.codec.reconstruct_tactic(
                    t, stripe, missing, data_only=True).result()
            payload = stripe[: t.N].reshape(-1).tobytes()
        if task.size > 0:
            payload = payload[: task.size]  # strip the EC stripe padding
        # a big-blob promote on a degraded stripe (gather + reconstruct)
        # can outlive one lease: renew before the replica writes, like
        # _migrate_disk renews per unit — a lost lease means the reaper
        # may have re-leased this task, and the re-execution owns it now
        if lease is not None and \
                not self.sched.renew_lease(task.task_id, lease):
            raise RuntimeError(
                f"lease {lease} lost mid-promote of ({task.vid}, {task.bid})")
        rt = get_tactic(CodeMode.Replica3)
        mat = np.frombuffer(payload, np.uint8).reshape(1, -1)
        full = self.codec.encode_tactic(rt, mat).result()
        hot_vol = self.cm.alloc_volume(int(CodeMode.Replica3))
        hot_bid, _ = self.cm.alloc_scope("bid", 1)
        wrote: set[int] = set()
        for i, unit in enumerate(hot_vol.units):
            node = self.nodes.get(unit.node_id)
            if node is None:
                continue
            try:
                node.create_vuid(unit.vuid, unit.disk_id)
                node.put_shard(unit.vuid, hot_bid, full[i].tobytes())
                wrote.add(i)
            except Exception:
                continue
        # shard 0 is NOT optional: the hot read path serves only the data
        # shard, so a redirect whose data replica never landed would send
        # every GET through a failed hot read before the EC fallback —
        # worse than no promotion at all
        if len(wrote) < rt.put_quorum or 0 not in wrote:
            # take the landed shards back out before failing: no redirect
            # references them, so nothing else ever would — and every
            # retry allocs a FRESH hot_bid, so leaked sets would pile up
            for i in wrote:
                unit = hot_vol.units[i]
                node = self.nodes.get(unit.node_id)
                if node is None:
                    continue
                try:
                    node.mark_delete_shard(unit.vuid, hot_bid)
                    node.delete_shard(unit.vuid, hot_bid)
                except Exception:
                    pass  # best effort; the write just succeeded here
            raise RuntimeError(
                f"hot promote of ({task.vid}, {task.bid}): wrote "
                f"{sorted(wrote)}/{rt.total} replicas, quorum "
                f"{rt.put_quorum} incl. the data shard")
        winner = self.cm.promote_blob(task.vid, task.bid, hot_vol.vid,
                                      hot_bid)
        if winner != (hot_vol.vid, hot_bid):
            # first committer won (a re-leased execution of this task beat
            # us past the lease backstop): OUR replica set is the orphan —
            # free it; the winner's redirect stands untouched
            for i in wrote:
                unit = hot_vol.units[i]
                node = self.nodes.get(unit.node_id)
                if node is None:
                    continue
                try:
                    node.mark_delete_shard(unit.vuid, hot_bid)
                    node.delete_shard(unit.vuid, hot_bid)
                except Exception:
                    pass
            return
        # delete-race re-check AFTER the commit: the deleter notes the key
        # BEFORE its own _drop_hot_copy, so either it sees our redirect
        # (and removes it) or we see its note here (and remove it) — a
        # promote racing a delete can never leave a dangling hot copy
        # serving a deleted blob's bytes
        if self.sched._recently_deleted(task.vid, task.bid):
            self.sched._drop_hot_copy(task.vid, task.bid)
            raise RuntimeError(
                f"blob ({task.vid}, {task.bid}) deleted during promote")
        registry("cache").counter("promotes").add()
        registry("cache").counter("promote_bytes").add(len(payload))
        from chubaofs_tpu.utils import events

        events.emit("tier_promote", entity=f"blob({task.vid},{task.bid})",
                    detail={"vid": task.vid, "bid": task.bid,
                            "hot_vid": hot_vol.vid, "hot_bid": hot_bid,
                            "bytes": len(payload)})

    # -- single-stripe shard repair -------------------------------------------

    def _usable(self, vol: VolumeInfo, idx: int) -> bool:
        """This stripe position can be read and written now: its node is
        routed and its disk NORMAL."""
        u = vol.units[idx]
        return u.node_id in self.nodes and self.cm.disk_serves(u.disk_id)

    def _repair_shards(self, vid: int, bid: int, bad_idx: list[int]):
        vol = self.cm.get_volume(vid)
        t = vol.tactic()
        # a position on a dark node or a broken disk cannot be written back
        # where it lives: the disk-level rebuild re-homes it
        unhandled = sorted(i for i in set(bad_idx) if self._usable(vol, i))
        if not unhandled:
            return
        if t.L:
            unhandled = self._repair_local_stripes(vol, t, bid, unhandled)
            if not unhandled:
                return
        if t.is_regenerating and len(unhandled) == 1:
            # the repair-traffic win: a single loss under a regenerating
            # mode downloads d beta payloads, not N full shards. Multi-loss
            # (or any helper failure) falls through to the generic gather.
            if self._repair_regenerating(vol, t, bid, unhandled[0]):
                return
        elif t.is_regenerating and len(unhandled) > 1:
            registry("scheduler").counter(
                "repair_beta_fallback", {"reason": "multi_loss"}).add()
        self._repair_global(vol, t, bid)

    def _repair_local_stripes(self, vol: VolumeInfo, t, bid: int,
                              bad_idx: list[int]) -> list[int]:
        """LRC local-stripe-first repair (work_shard_recover.go:517
        recoverByLocalStripe): for each AZ whose damage fits its local parity
        budget, repair reading ONLY that AZ's shards. Returns the reported bad
        indexes that still need the global path."""
        span = trace.current_span()
        leftover: list[int] = []
        for idx, local_n, local_m in t.local_stripes():
            az_reported = [i for i in bad_idx if i in idx]
            if not az_reported:
                continue
            reads = self._probe(vol, bid, idx, span=span)  # same-AZ reads only
            az_bad = [i for i in idx if i not in reads]
            if not az_bad:
                continue
            if len(az_bad) > local_m:
                leftover.extend(az_reported)  # beyond local budget
                continue
            # the AZ's local stripe is RS(local_n, local_m) over `idx`: the
            # lost rows from its first local_n readable ones
            present, sub = self._local_rows(idx, local_n, reads)
            rows = self.codec.decode_rows(
                local_n, local_m, present, sub,
                [idx.index(g) for g in az_bad]).result()
            for p, g in enumerate(az_bad):
                self._write_back(vol, g, bid, rows[p].tobytes())
            # the repair-traffic win the LRC layout buys: these shards were
            # healed reading ONE local group, not the global stripe
            registry("scheduler").counter(
                "repair_local_shards").add(len(az_bad))
        return leftover

    @staticmethod
    def _local_stripe_of(t, index: int) -> tuple[list[int], int, int]:
        """(positions, local_n, local_m) of the AZ-local stripe that holds
        stripe position `index` (Tactic.local_stripes)."""
        return next(ls for ls in t.local_stripes() if index in ls[0])

    def _local_rows(self, idx: list[int], local_n: int, reads: dict):
        """The first local_n rows of the AZ stripe `idx` that `reads` holds, in
        stripe order, for CodecService.decode_rows(local_n, local_m, ...):
        (their positions in the LOCAL stripe's own coordinates, (local_n, k)
        bytes in a slot the codec lends). Every stripe of a unit has the same
        rows where nothing else is damaged, and with them the same decode
        matrix."""
        srv = [g for g in idx if g in reads][:local_n]
        return [idx.index(g) for g in srv], self.codec.slot_of(
            [reads[g] for g in srv])

    def _repair_global(self, vol: VolumeInfo, t, bid: int):
        """Global-stripe repair + recompute of any missing local parities."""
        span = trace.current_span()
        stripe, present, shard_len = self._gather(vol, t, bid, span=span)
        missing = [i for i in range(t.N + t.M) if i not in present]
        if missing:
            if t.is_regenerating:  # any-N decode through the PM generator
                stripe = self.codec.reconstruct_tactic(
                    t, stripe, missing).result()
            else:
                srv = present[: t.N]
                stripe[missing] = self.codec.decode_rows(
                    t.N, t.M, srv, stripe[srv], missing).result()
            for idx in missing:
                self._write_back(vol, idx, bid, stripe[idx].tobytes())
            registry("scheduler").counter(
                "repair_global_shards").add(len(missing))
        if t.L:
            # local parities live outside the global stripe: any missing one is
            # recomputed from its AZ's (now whole) global shards
            local_idx = list(range(t.global_count, t.total))
            have = self._probe(vol, bid, local_idx, span=span)
            lost_azs = {t.az_of_shard(i) for i in local_idx if i not in have}
            local_n = (t.N + t.M) // t.az_count
            local_m = t.L // t.az_count
            for idx, _, _ in t.local_stripes():
                az = t.az_of_shard(idx[0])
                if az not in lost_azs:
                    continue
                src = stripe[idx[:local_n]]
                full = self.codec.encode(local_n, local_m, src).result()
                for p, g in enumerate(idx[local_n:]):
                    if g not in have:
                        self._write_back(vol, g, bid, full[local_n + p].tobytes())

    def _write_back(self, vol: VolumeInfo, idx: int, bid: int, payload: bytes):
        """Idempotent by construction: put_shard over an existing bid punches
        the superseded record and appends the same bytes, so a re-executed
        task (lease expiry, crash-restart) can never corrupt the stripe. A
        position that cannot be written where it lives (dark node, broken
        disk) is the disk-level rebuild's."""
        if not self._usable(vol, idx):
            return
        unit = vol.units[idx]
        node = self.nodes[unit.node_id]
        node.create_vuid(unit.vuid, unit.disk_id)
        node.put_shard(unit.vuid, bid, payload)
        registry("scheduler").counter("repaired_shards").add()

    def _read_one(self, vol: VolumeInfo, idx: int, bid: int) -> bytes:
        unit = vol.units[idx]
        node = self.nodes.get(unit.node_id)
        if node is None:
            raise ConnectionError(f"node {unit.node_id} unknown")
        return node.get_shard(unit.vuid, bid)

    def _drain_reads(self, futs: dict, out: dict, need: int | None = None) -> list:
        """Drain a {key: Future-of-bytes} fan-out under ONE shared
        read_deadline: successes land in `out` and feed the repair-traffic
        byte accounting; absent/unreachable/hung reads are returned as
        leftover keys, counted by failure class
        (cfs_scheduler_probe_fail{reason}) so a silent hang and a real bug
        stop being indistinguishable. The one timeout/cancel/classify
        block both _probe and _copy_direct ride — their semantics must
        never diverge.

        `need` is how many successes the decode strictly requires: bytes
        beyond it are HEDGES (straggler insurance) and count to
        repair_bytes_hedged instead of repair_bytes_downloaded, so
        bytes-per-repaired-shard stays an honest numerator. None = every
        read is required."""
        reg = registry("scheduler")
        deadline = time.monotonic() + self.read_deadline
        leftover = []
        got = 0
        for key, f in futs.items():
            try:
                data = f.result(timeout=max(0.0, deadline - time.monotonic()))
            except FutureTimeout:
                f.cancel()  # queued laggards release their pool slot
                reg.counter("probe_fail", {"reason": "timeout"}).add()
                leftover.append(key)
                continue
            except Exception as e:
                reg.counter("probe_fail",
                            {"reason": classify_io_error(e)}).add()
                leftover.append(key)
                continue
            out[key] = data
            got += 1
            if need is not None and got > need:
                reg.counter("repair_bytes_hedged").add(len(data))
            else:
                reg.counter("repair_bytes_downloaded").add(len(data))
        return leftover

    def _probe(self, vol: VolumeInfo, bid: int, idxs,
               span=None, need: int | None = None) -> dict[int, bytes]:
        """Read the given stripe positions CONCURRENTLY via _drain_reads;
        the whole fan-out lands on the span as a `download` stage."""
        idxs = list(idxs)
        if not idxs:
            return {}
        t0 = time.perf_counter()
        futs = {i: self._shard_pool.submit(self._read_one, vol, i, bid)
                for i in idxs}
        reads: dict[int, bytes] = {}
        self._drain_reads(futs, reads, need=need)
        if span is not None:
            span.add_stage("download", start=t0)
        return reads

    def _gather(self, vol: VolumeInfo, t, bid: int, span=None):
        """Read every readable global shard of a stripe; infer shard_len.
        Decode needs only N rows — the extra M reads are hedges and are
        accounted as such (_drain_reads need=N)."""
        reads = self._probe(vol, bid, range(t.N + t.M), span=span, need=t.N)
        if len(reads) < t.N:
            raise RuntimeError(f"stripe {vol.vid}/{bid}: {len(reads)} < N={t.N} readable")
        shard_len = len(next(iter(reads.values())))
        stripe = np.zeros((t.N + t.M, shard_len), np.uint8)
        for idx, data in reads.items():
            stripe[idx] = np.frombuffer(data, np.uint8)
        return stripe, sorted(reads), shard_len

    # -- beta-fetch repair (regenerating modes, codec/pm.py) -------------------

    def _read_combined(self, vol: VolumeInfo, idx: int, bid: int,
                       coeffs: bytes) -> bytes:
        unit = vol.units[idx]
        node = self.nodes.get(unit.node_id)
        if node is None:
            raise ConnectionError(f"node {unit.node_id} unknown")
        return node.get_shard_combined(unit.vuid, bid, coeffs)

    def _gather_beta(self, vol: VolumeInfo, t, bid: int, fail: int,
                     span=None):
        """Beta-fetch gather for a SINGLE lost shard of a regenerating
        stripe: the layout-aware helper set (Tactic.helper_set — same-AZ
        first) each ships its beta = shard/alpha combined payload
        (BlobNode.get_shard_combined). Returns (helpers, payloads (d, beta))
        or None when the survivors can't field d helpers or any helper read
        fails — the caller then falls back to the full-stripe gather, which
        needs only N of the survivors."""
        from chubaofs_tpu.codec import pm

        reg = registry("scheduler")

        alive = [i for i in range(t.global_count)
                 if i != fail and self._usable(vol, i)]
        helpers = t.helper_set(fail, alive)
        if not helpers:
            reg.counter("repair_beta_fallback",
                        {"reason": "helpers_short"}).add()
            return None
        kernel = pm.get_kernel(t.total, t.N)
        coeffs = kernel.helper_coeffs(fail).tobytes()
        t0 = time.perf_counter()
        futs = {i: self._shard_pool.submit(
                    self._read_combined, vol, i, bid, coeffs)
                for i in helpers}
        reads: dict[int, bytes] = {}
        # every helper is load-bearing (the repair matrix inverts exactly
        # these d rows): need=len so none of these bytes count as hedged
        self._drain_reads(futs, reads, need=len(helpers))
        if span is not None:
            span.add_stage("download", start=t0)
        if len(reads) < len(helpers):
            reg.counter("repair_beta_fallback", {"reason": "read_fail"}).add()
            return None
        payloads = np.stack(
            [np.frombuffer(reads[i], np.uint8) for i in helpers])
        from chubaofs_tpu.codec.codemode import CodeMode

        reg.counter("repair_helper_bytes",
                    {"mode": CodeMode(vol.code_mode).name}).add(
            int(payloads.size))
        return helpers, payloads

    def _repair_regenerating(self, vol: VolumeInfo, t, bid: int,
                             fail: int) -> bool:
        """Single-loss beta repair: d combined sub-shard reads, ONE
        (alpha, d) matmul decode through the codec service, write back.
        Returns False (nothing written) when the beta path can't run —
        _repair_global then handles the stripe generically."""
        from chubaofs_tpu.codec import pm

        span = trace.current_span()
        got = self._gather_beta(vol, t, bid, fail, span=span)
        if got is None:
            return False
        helpers, payloads = got
        kernel = pm.get_kernel(t.total, t.N)
        mat = kernel.repair_matrix(fail, helpers)
        fixed = self.codec.matmul(mat, payloads).result()
        self._write_back(vol, fail, bid, fixed.reshape(-1).tobytes())
        registry("scheduler").counter("repair_beta_shards").add()
        return True

    # -- disk-level migrate (bulk; the 10k-stripe batch path) ------------------

    def _migrate_disk(self, task: Task, lease: int | None = None):
        """Move every stripe position off a disk.

        Order matters, per unit (migrate.go's prepare / work / finish): pick
        the destination and open the unit's NEW chunk there (the vuid of its
        next epoch, which no reader knows yet); copy or rebuild every row
        through the OLD mapping and write it into that chunk; only then
        re-home the unit in clustermgr, in one step. A reader meets either
        the old unit (dark: it decodes around it) or the new one with every
        shard in place, never a unit half written. A crash mid-task leaves
        every uncommitted unit's old mapping intact and the task retryable."""
        source_broken = self.cm.disks[task.disk_id].status != DISK_NORMAL
        try:
            for vol, unit in self.cm.volumes_on_disk(task.disk_id):
                # a disk migrate routinely outlives one lease: renew per unit
                # (and, inside a long unit, by the clock: _keepalive) so a
                # HEALTHY worker never races the reaper; a lost lease (we were
                # reaped and possibly re-leased) aborts — the work is someone
                # else's now, and idempotent write-back keeps the abort safe
                if lease is not None:
                    if not self.sched.renew_lease(task.task_id, lease):
                        raise RuntimeError(
                            f"lease {lease} lost mid-migrate of disk {task.disk_id}")
                    self._lease = (task.task_id, lease, time.monotonic())
                # re-homed as soon as it is whole: readers stop decoding
                # around a unit the moment its last row is in place
                self._commit_unit(
                    self._prepare_unit(vol, unit, task.disk_id, source_broken),
                    task.disk_id)
        finally:
            self._lease = None
        # DROPPED only here: every unit the disk held is committed
        self.cm.set_disk_status(task.disk_id, DISK_DROPPED)

    def _balance_unit(self, task: Task):
        """Move ONE volume unit off an (otherwise healthy) overloaded disk."""
        vol = self.cm.get_volume(task.vid)
        unit = next((u for u in vol.units if u.disk_id == task.disk_id), None)
        if unit is None:
            # a previous attempt already re-homed the mapping but may have
            # died mid-copy (mapping updates before the shard writes): sweep
            # the volume's stripes through the repair plane rather than
            # declaring victory over a silently degraded stripe
            self._enqueue_missing(vol)
            return
        source_broken = self.cm.disks[task.disk_id].status != DISK_NORMAL
        prep = self._prepare_unit(vol, unit, task.disk_id, source_broken,
                                  dest_disk_id=task.dest_disk_id)
        self._commit_unit(prep, task.disk_id)

    def _enqueue_missing(self, vol: VolumeInfo):
        """Probe every stripe position of every bid in the volume; feed any
        missing/unreadable position to the repair topic."""
        t = vol.tactic()
        bids: set[int] = set()
        for u in vol.units:
            node = self.nodes.get(u.node_id)
            if node is None:
                continue
            try:
                bids.update(m.bid for m in node.list_shards(u.vuid))
            except Exception:
                continue
        for bid in sorted(bids):
            have = self._probe(vol, bid, range(t.total))
            bad = [i for i in range(t.total) if i not in have]
            if bad:
                self.sched.proxy.send_shard_repair(vol.vid, bid, bad,
                                                   "balance_retry")

    def _copy_direct(self, vol: VolumeInfo, unit, bids: list[int],
                     prep: dict) -> list[int]:
        """Healthy-source fast path: CONCURRENT bounded reads of the unit's
        own rows via _drain_reads (a serial loop here would pay
        read_deadline per slow bid, not per unit), written to the new chunk.
        Returns the bids that still need the gather/reconstruct pipeline."""
        node = self.nodes.get(unit.node_id)
        if node is None:
            return list(bids)
        futs = {bid: self._shard_pool.submit(node.get_shard, unit.vuid, bid)
                for bid in bids}
        rows: dict[int, bytes] = {}
        left = self._drain_reads(futs, rows)
        for bid, payload in rows.items():
            self._put_row(prep, bid, payload)
        return left

    def _read_first(self, vol: VolumeInfo, bid: int, cands: list[int],
                    need: int, span=None) -> dict[int, bytes]:
        """Reads of the first `need` of `cands` that answer: the first `need`
        are launched, and only what fails is replaced from the rest. Fewer
        than `need` entries where the candidates run out."""
        reads: dict[int, bytes] = {}
        tried = 0
        while len(reads) < need and tried < len(cands):
            batch = cands[tried: tried + need - len(reads)]
            tried += len(batch)
            reads.update(self._probe(vol, bid, batch, span=span))
        return reads

    def _count_rebuild_reads(self, vol: VolumeInfo, unit, reads: dict) -> None:
        """Survivor bytes a rebuild's gather read, and those of them that came
        from a disk of another AZ than the rebuilt unit's (the inter-AZ link an
        LRC mode's local parities are bought to spare)."""
        reg = registry("scheduler")
        disks = self.cm.disks
        az = disks[unit.disk_id].az
        reg.counter("rebuild_bytes", {"kind": "read"}).add(
            sum(len(b) for b in reads.values()))
        reg.counter("rebuild_cross_az_bytes").add(
            sum(len(b) for i, b in reads.items()
                if disks[vol.units[i].disk_id].az != az))

    def _gather_rows(self, vol: VolumeInfo, t, unit, bid: int, span=None):
        """Exactly N survivor rows of a stripe for the rebuild of `unit`:
        (global positions, (N, k) bytes in that order). Reads the first N
        positions that can answer (routed node, NORMAL disk) and replaces only
        what fails, so every stripe of a unit has the same survivor set, and
        with it the same decode matrix, wherever nothing else is damaged. The
        rebuild reads N shards a stripe and no more."""
        with trace.stage("repair.gather"):
            cands = [i for i in range(t.N + t.M)
                     if i != unit.index and self._usable(vol, i)]
            reads = self._read_first(vol, bid, cands, t.N, span=span)
            if len(reads) < t.N:
                raise RuntimeError(
                    f"stripe {vol.vid}/{bid}: {len(reads)} < N={t.N} readable")
            present = sorted(reads)
            self._count_rebuild_reads(vol, unit, reads)
            return present, self.codec.slot_of([reads[i] for i in present])

    def _gather_local_rows(self, vol: VolumeInfo, t, unit, bid: int, span=None):
        """Local-stripe-first (work_shard_recover.go:517 recoverByLocalStripe,
        tried before recoverByGlobalStripe): local_n rows of the unit's OWN
        AZ's local stripe (its other globals and its local parity), read from
        that AZ alone: (their positions in the local stripe's coordinates,
        (local_n, k) bytes). None where the AZ's stripe has a second hole
        (another unit of it unrouted, on a disk that is not NORMAL, or
        unreadable): the caller takes the global gather."""
        idx, local_n, _ = self._local_stripe_of(t, unit.index)
        cands = [i for i in idx if i != unit.index and self._usable(vol, i)]
        if len(cands) < local_n:
            return None  # a hole known without a read
        with trace.stage("repair.gather_local"):
            reads = self._read_first(vol, bid, cands, local_n, span=span)
            self._count_rebuild_reads(vol, unit, reads)
            if len(reads) < local_n:
                return None
            return self._local_rows(idx, local_n, reads)

    def _gather_for_unit(self, vol: VolumeInfo, t, unit, bid: int,
                         span=None):
        """Mode-aware stripe gather for the migrate/rebuild pipeline: a
        regenerating volume first tries the beta-fetch for the migrating
        unit's row (d combined payloads instead of a full-stripe gather —
        the bulk-rebuild path is where nearly all repair bytes move) and
        falls back to the full gather when helpers can't cover it. An RS
        global unit gathers N survivors; an LRC global unit its AZ's local
        stripe first, as upstream's repair worker does (local_n reads, none
        across the AZ boundary), and N survivors of any AZ only where that
        stripe has a second hole (counted: rebuild_local_fallbacks, one a
        stripe); an LRC local parity gathers its AZ's local stripe, and N
        survivors only where that has holes."""
        if t.is_regenerating:
            if unit.index < t.global_count:
                got = self._gather_beta(vol, t, bid, unit.index, span=span)
                if got is not None:
                    return ("beta",) + got
            return ("full", self._gather(vol, t, bid, span=span))
        if unit.index < t.N + t.M:
            if t.L:
                got = self._gather_local_rows(vol, t, unit, bid, span=span)
                if got is not None:
                    return ("local_rows",) + got
                registry("scheduler").counter("rebuild_local_fallbacks").add()
            return ("rows",) + self._gather_rows(vol, t, unit, bid, span=span)
        idx, local_n, _ = self._local_stripe_of(t, unit.index)
        have = self._probe(vol, bid, [i for i in idx[:local_n] if self._usable(vol, i)],
                           span=span)
        survivors = None
        if len(have) < local_n:
            survivors = self._gather_rows(vol, t, unit, bid, span=span)
        return ("local", idx, have, survivors)

    def _submit_row(self, vol: VolumeInfo, t, unit, bid: int, gathered):
        """Turn one gathered stripe into the migrating unit's row: its bytes,
        or a _PendingRow of them. A lost global shard is ONE row of the degraded
        GET's decode ((1, N) @ (N, k) through CodecService.decode_rows: every
        stripe of the unit shares the matrix, so the jobs batch by content),
        or of its AZ's local stripe's ((1, local_n) @ (local_n, k)) where the
        gather took that; a lost local parity decodes what its AZ's local stripe lacks and
        re-encodes it. A beta-gather (regenerating modes) becomes the
        (alpha, d) repair matmul — batchable on the device like the decodes."""
        reg = registry("scheduler")
        kind = gathered[0]
        if kind == "beta":
            _, helpers, payloads = gathered
            from chubaofs_tpu.codec import pm

            kernel = pm.get_kernel(t.total, t.N)
            mat = kernel.repair_matrix(unit.index, helpers)
            reg.counter("repair_beta_shards").add()
            return _PendingRow(self.codec.matmul(mat, payloads),
                         lambda out: out.reshape(-1).tobytes())
        if kind == "full":  # a regenerating stripe the beta-fetch cannot cover
            stripe, present, _ = gathered[1]
            if unit.index in present:
                return stripe[unit.index].tobytes()
            # repair with the FULL missing set: zero-filled absent rows
            # must never be treated as survivors
            missing = [i for i in range(t.N + t.M) if i not in present]
            return _PendingRow(self.codec.reconstruct_tactic(t, stripe, missing),
                         lambda fixed: fixed[unit.index].tobytes())
        if kind == "rows":
            _, present, survivors = gathered
            reg.counter("rebuild_decode_jobs").add()
            return _PendingRow(self.codec.decode_rows(t.N, t.M, present, survivors,
                                                [unit.index]),
                         lambda rows: rows[0].tobytes())
        if kind == "local_rows":
            # the one lost row of RS(local_n, local_m), in the local stripe's
            # own coordinates: one matrix a unit here too
            _, present, survivors = gathered
            idx, local_n, local_m = self._local_stripe_of(t, unit.index)
            reg.counter("rebuild_decode_jobs").add()
            reg.counter("rebuild_local_jobs").add()
            return _PendingRow(self.codec.decode_rows(local_n, local_m, present, survivors,
                                                [idx.index(unit.index)]),
                         lambda rows: rows[0].tobytes())
        # LRC local parity: complete its AZ's local stripe, then re-encode
        _, idx, have, survivors = gathered
        local_m = t.L // t.az_count
        src = idx[: len(idx) - local_m]
        lost = [i for i in src if i not in have]
        rows = {i: np.frombuffer(have[i], np.uint8) for i in have}
        if lost:
            present, surv = survivors
            reg.counter("rebuild_decode_jobs").add()
            got = self.codec.decode_rows(t.N, t.M, present, surv, lost).result()
            rows.update({i: got[p] for p, i in enumerate(lost)})
        full = self.codec.encode(
            len(src), local_m, np.stack([rows[i] for i in src])).result()
        return full[len(src) + idx[len(src):].index(unit.index)].tobytes()

    def _put_row(self, prep: dict, bid: int, payload: bytes) -> None:
        """One row into the unit's new chunk (CRC-framed and verified by the
        blobnode like any shard write)."""
        with trace.stage("repair.write_back"):
            prep["node"].put_shard(prep["vuid"], bid, payload)
        reg = registry("scheduler")
        reg.counter("repaired_shards").add()
        reg.counter("rebuild_bytes", {"kind": "written"}).add(len(payload))

    def _land_oldest(self, prep: dict, inflight: deque) -> None:
        """Wait for the unit's oldest decode and write its row."""
        self._keepalive()
        bid, row = inflight.popleft()
        if isinstance(row, _PendingRow):
            with trace.stage("repair.decode_wait"):
                row = row.result()
        self._put_row(prep, bid, row)

    def _rebuild_rows(self, vol: VolumeInfo, t, unit, bids: list[int],
                      prep: dict):
        """The windowed rebuild pipeline (the _put_pipelined window pattern
        applied to repair-GET): up to repair_window stripes' survivor
        gathers run on the stripe pool while earlier stripes' decodes drain
        through the codec service's device batches (at most DECODE_AHEAD of
        them in flight a unit) and their rows go to the new chunk — downloads
        never idle waiting on decode, decode never starves waiting on the
        network. Consumption is bid order, so write-back order is
        deterministic; every row is in the chunk on return. A stripe that
        cannot be gathered because its blob was deleted meanwhile is skipped
        (_commit_unit records the delete); any other one fails the task.
        repair_window <= 1 degenerates to the serial control path."""
        if not bids:
            return
        span = trace.current_span()
        window = self.repair_window
        inflight: deque = deque()

        def gather(bid: int):
            try:
                return self._gather_for_unit(vol, t, unit, bid, span=span)
            except Exception:
                if self._deleted(vol, bid):
                    return None
                raise

        def decode(bid: int, gathered) -> None:
            if gathered is None:
                return
            inflight.append((bid, self._submit_row(vol, t, unit, bid, gathered)))
            while inflight and (len(inflight) > self.DECODE_AHEAD
                                or not isinstance(inflight[0][1], _PendingRow)
                                or inflight[0][1].done()):
                self._land_oldest(prep, inflight)

        def gather_job(bid: int):
            # the task span follows the gather onto the pool worker so its
            # download stage (and any failpoint evidence) lands on the trace
            if span is not None:
                trace.push_span(span)
            try:
                return gather(bid)
            finally:
                if span is not None:
                    trace.pop_span()

        if window <= 1:
            for bid in bids:
                decode(bid, gather(bid))
        else:
            occ = registry("scheduler").summary("rebuild_window_occupancy",
                                                buckets=BATCH_BUCKETS)
            pending: deque = deque()
            it = iter(bids)
            nxt = next(it, None)
            while pending or nxt is not None:
                while nxt is not None and len(pending) < window:
                    pending.append((nxt, self._stripe_pool.submit(gather_job, nxt)))
                    nxt = next(it, None)
                occ.observe(len(pending))
                bid, f = pending.popleft()
                decode(bid, f.result())
        while inflight:
            self._land_oldest(prep, inflight)

    def _deleted(self, vol: VolumeInfo, bid: int) -> bool:
        """The deleter has this blob: noted by a sweep of this process, or
        tombstoned on a unit that can say so (a tombstone ANYWHERE means the
        bid was deleted: inspect_volumes reads them the same way)."""
        return self.sched._recently_deleted(vol.vid, bid) or any(
            self.sched._has_tombstone(u.node_id, u.vuid, bid) for u in vol.units)

    def _tombstones(self, vol: VolumeInfo) -> set[int]:
        """Every bid some reachable unit of the volume holds a tombstone for."""
        out: set[int] = set()
        for u in vol.units:
            node = self.nodes.get(u.node_id)
            if node is not None:
                try:
                    out |= node.tombstones_of(u.vuid)
                except Exception:
                    pass  # no chunk there (never written, or dropped)
        return out

    def _carry_deletes(self, prep: dict) -> None:
        """Deletes travel with the unit: every bid the volume's reachable
        units hold a tombstone for, and every row of the new chunk whose blob
        the deleter has taken since it was written, is deleted in the new
        chunk too. A rebuild runs beside the deleter: a delete that punched
        only the old units must not leave its shard in the new one, and one
        whose only tombstones move must not be resurrected."""
        vol, node, vuid = prep["vol"], prep["node"], prep["vuid"]
        gone = self._tombstones(vol)
        gone.update(b for b in prep["bids"]
                    if self.sched._recently_deleted(vol.vid, b))
        gone -= prep["carried"]
        prep["carried"] |= gone
        for bid in gone:
            try:
                node.mark_delete_shard(vuid, bid)
                node.delete_shard(vuid, bid)
            except Exception:
                node.tombstone_shard(vuid, bid)  # never stored here

    def _prepare_unit(self, vol: VolumeInfo, unit, source_disk_id: int,
                      source_broken: bool,
                      dest_disk_id: int | None = None) -> dict:
        """Phases 1 and 2 of a unit move: pick the destination, open the
        unit's NEW chunk there (the vuid of its next epoch), then copy or
        rebuild every row into it. No cluster state changes here: a crash
        after prepare leaves the old mapping untouched and a chunk no reader
        knows."""
        t = vol.tactic()
        # every bid in this volume, seen from any unit (source included when healthy)
        bids: set[int] = set()
        for u in vol.units:
            if u.disk_id == source_disk_id and source_broken:
                continue
            node = self.nodes.get(u.node_id)
            if node is None:
                continue
            try:
                bids.update(m.bid for m in node.list_shards(u.vuid))
            except Exception:
                continue
        # source copies or rebuilt rows. Tombstones TRAVEL with the unit —
        # enumerated DIRECTLY from the chunks (they are invisible to
        # list_shards, so deriving them from live bids would drop any delete
        # whose bid no reachable unit still serves): _commit_unit carries them
        tombstoned = self._tombstones(vol)
        dest = dest_disk_id
        if dest is not None:
            # a destination pinned at scheduling time may have gone stale
            d = self.cm.disks.get(dest)
            if d is None or d.status != DISK_NORMAL or \
                    dest in {u.disk_id for u in vol.units}:
                dest = None
        if dest is None:
            dest = self._dest_for(vol, source_disk_id)
        vuid = make_vuid(vol.vid, unit.index, unit.epoch + 1)
        node = self.nodes[self.cm.disks[dest].node_id]
        node.drop_vuid(vuid)  # an aborted attempt's chunk: start it clean
        node.create_vuid(vuid, dest)
        work = [b for b in sorted(bids) if b not in tombstoned]
        prep = {"vol": vol, "unit": unit, "dest": dest, "vuid": vuid,
                "node": node, "bids": work, "carried": set()}
        if not source_broken:
            work = self._copy_direct(vol, unit, work, prep)
        self._rebuild_rows(vol, t, unit, work, prep)
        return prep

    def _commit_unit(self, prep: dict, source_disk_id: int):
        """Phase 3: re-home the unit in clustermgr — one step, after every
        shard and every delete is in the new chunk, so the next read of the
        unit finds it whole. The deleter notes a delete before it looks up the
        units to punch, so one that raced the re-home and punched only the old
        unit is seen by the second _carry_deletes."""
        vol, unit = prep["vol"], prep["unit"]
        with trace.stage("repair.commit"):
            self._carry_deletes(prep)
            if vol.units[unit.index].vuid != unit.vuid:
                raise RuntimeError(
                    f"unit {vol.vid}/{unit.index} was re-homed under its migrate")
            self.cm.update_volume_unit(vol.vid, unit.index, prep["dest"])
            self._carry_deletes(prep)
            registry("scheduler").counter("rebuild_units_committed").add()
            # the move must FREE the source: drop the superseded chunk (best
            # effort — an unreachable source just leaks until re-imaged, and a
            # disk that is not NORMAL is not touched at all)
            old_node = self.nodes.get(unit.node_id)
            if old_node is not None and self.cm.disk_serves(unit.disk_id):
                try:
                    old_node.drop_vuid(unit.vuid)
                except Exception:
                    pass

    def _dest_for(self, vol: VolumeInfo, source_disk_id: int) -> int:
        vol_disks = {u.disk_id for u in vol.units}
        return self.sched.pick_dest_disk(
            exclude=vol_disks | {source_disk_id},
            az=self.cm.disks[source_disk_id].az,
        )


class Reclaimer(_OwnThread):
    """The reclaim plane's own worker: the blob deleter (blob_deleter.go's
    Kafka consumer) and, behind it, each node's chunk compaction.

    Woken by the background tick's kick (a backlog a restart found, a switch
    released) and, in a daemon, by the blob_delete topic itself (follow_topic:
    every produce kicks; an in-process driver steps it by
    MiniCluster.run_background_once instead, so a test decides when a delete
    is applied). A drain empties the topic (Scheduler.run_deleter, its units
    side by side on the `reclaim-io` pool) and compacts what the rule picks
    (BlobNode.compaction_candidates; a compaction copies outside its chunk's lock),
    one chunk between two looks at the topic. Neither the tick nor the lock
    a tick runs under ever waits for either, and with nothing to delete or
    compact the thread sleeps."""

    thread_name = "reclaim-worker"
    IO_WORKERS = 8  # a drain's units side by side: lock, index batch and punches release the interpreter lock

    def __init__(self, sched: Scheduler, nodes: dict[int, BlobNode]):
        super().__init__()
        self.sched = sched
        self.nodes = nodes
        self.compacted = 0  # bytes the drains' compactions reclaimed
        self._pool = ThreadPoolExecutor(
            max_workers=self.IO_WORKERS, thread_name_prefix="reclaim-io")
        trace.declare_stages(("deleter.batch", "deleter.apply", "chunk.delete", "chunk.delete_wait",
                              "chunk.compact", "chunk.compact_swap"))

    def follow_topic(self) -> None:
        """From now on every message produced to blob_delete wakes the
        thread: a DELETE is applied as fast as the topic fills, with no tick
        to wait for. What the daemon does at boot."""
        self.sched.proxy.topics[TOPIC_BLOB_DELETE].subscribe(self.kick)
        self.kick()  # and whatever a restart found there

    def _drain(self) -> None:
        """Until the topic is empty and the rule picks no chunk: the deleter
        first, and ONE chunk's compaction between two looks at the topic (a
        volume's sixteen chunks cross the rule together; their copies in a
        row would hold the deleter for longer than a DELETE may wait)."""
        while not self._stop.is_set():
            n = self.sched.run_deleter(pool=self._pool)
            if n:
                self._count(n)
            elif not self._compact_one():
                break

    def _compact_one(self) -> bool:
        # compaction is host-local work: a dark/dead node skips its own sweep
        # without stalling the cluster's (the daemon analog runs it per host)
        for node in list(self.nodes.values()):
            try:
                chunk = next(node.compaction_candidates(), None)
                got = chunk.compact() if chunk is not None else 0
            except Exception:
                continue
            if got:
                self.compacted += got
                return True
        return False

    def close(self) -> None:
        """Stop the thread (a drain in flight ends after its batch; what it
        had not committed is consumed again: both phases are idempotent)."""
        self._join()
        self._pool.shutdown(wait=False)
