"""Expiry drive: the old generation expired to its END under the writers, then
everything compared with the plain store model.

    python -m chubaofs_tpu.tools.expiredrive --root /tmp/expire              # needs the TPU
    python -m chubaofs_tpu.tools.expiredrive --root /tmp/expire --jax-platform cpu --objects 24

Layout, objects, streams and `apply_within_s` come from the benchmark's
configuration and traffic file of this deployment
(benchmark/configs/az1-ec12p4-expire.json, benchmark/traffic/put16m-expire.json:
the timed cell az1.put16m-expire), so this drive and the cell state one
deployment; `--objects` overrides one of them. The daemon boots in this process
exactly as `chubaofs-tpu -c blobstore.json` boots it (cmd.start_role); clients
speak HTTP to its gateway. Set-up loads the old generation; then the cell's
streams PUT, each PUT followed by a DELETE of the oldest loaded object, until
ALL of the old generation is deleted (the cell's window closes long before); a
prober GETs deleted and live objects as the cell's does; and, because the
compaction rule picks no chunk at this scale (PERF.md), a thread compacts
those chunks of `--compact-nodes` of which a quarter is holes (Chunk.compact
on the chunks it chooses; `--compact-nodes ""` leaves compaction to the rule
as committed, which the reclaim worker applies by itself), so that live
records are copied, caught up and swapped while their chunks are written and
punched. Then
quiescence (the blob_delete topic empty, every blob applied) and the
comparison with benchmark/reference_expire.py and reference.py: every deleted
object not-found; every object written meanwhile byte-equal and EVERY shard of
it equal to the reference row, in chunks that were compacted and in chunks
that were not; the live records' bytes equal to the model's live stored bytes
(plus the shards a volume had taken of a stripe when its chunks filled and the
blob moved on: counted, and each one a written object's bid); every byte of a
record made a hole (`cfs_blobnode_hole_bytes`), and of the copies a compaction
wrote of records that died under it
(`cfs_blobnode_compact_bytes{kind="garbage"}`), either given back to the
filesystem (`cfs_blobnode_released_bytes`: punched, dropped with its extent,
dropped by a compaction) or still held beside the live records
(`dead_bytes_held`: at most one part-dead extent a chunk where the filesystem
refuses the punch, none where it takes it), and either all of the punches
taken or all refused. Nothing is timed for a result. One JSON line on stdout;
exit 1 and `"ok": false` if anything differs."""

from __future__ import annotations

import argparse
import json
import struct
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chubaofs_tpu.tools.rebuilddrive import _bench_module, _cell_files

CONFIG_NAME = "az1-ec12p4-expire"


def drive(root: str, platform: str | None, objects: int | None, seed: int,
          timeout_s: float, compact_nodes: list[int]) -> dict:
    from chubaofs_tpu import cmd
    from chubaofs_tpu.blobstore.access import AccessError
    from chubaofs_tpu.blobstore.clustermgr import parse_vuid
    from chubaofs_tpu.blobstore.gateway import AccessClient
    from chubaofs_tpu.ops import device
    from chubaofs_tpu.utils.exporter import registry

    reference = _bench_module("reference")
    model_of = _bench_module("reference_expire")
    config, params = _cell_files(CONFIG_NAME)
    lay = config["layout"]
    n_old = objects or params["objects"]
    size, streams = params["object_bytes"], params["streams"]
    device.request_platform(platform)
    device.enable_compile_cache()
    cfg = {"role": "blobstore", "root": root, "listen": "127.0.0.1:0",
           "nodes": lay["nodes"], "disksPerNode": lay["disks_per_node"], "azs": lay["azs"]}
    if platform:
        cfg["jaxPlatform"] = platform
    daemon = cmd.start_role(cfg)
    out: dict = {"boot": dict(daemon.boot_info), "config": CONFIG_NAME, "objects": n_old,
                 "object_bytes": size, "seed": seed, "compact_nodes": compact_nodes}
    errors: list[str] = []
    try:
        cluster = daemon.runner.handles["cluster"]
        bases = [np.random.default_rng([seed, 0xE59, i]).bytes(size) for i in range(4)]

        def payload(a: int, b: int) -> bytes:
            return struct.pack("<QII", seed, a, b) + bases[(a + b) % 4][16:]

        def guarded(target, *args) -> None:
            try:
                target(*args)
            except Exception as e:  # a failed client is the drive's failure
                errors.append(f"{type(e).__name__}: {e}")

        def every(n: int, target, *args) -> None:
            with ThreadPoolExecutor(n) as pool:
                for s in range(n):
                    pool.submit(guarded, target, s, *args)

        # -- the old generation, as the cell's set-up loads it -------------------
        old: list = [None] * n_old
        nxt, lock = iter(range(n_old)), threading.Lock()

        def loader(_: int) -> None:
            c = AccessClient([daemon.addr])
            while True:
                with lock:
                    i = next(nxt, None)
                if i is None:
                    return
                old[i] = c.put(payload(0, i))

        t0 = time.monotonic()
        every(params["load_streams"], loader)
        out["load_s"] = time.monotonic() - t0
        def chunks_now() -> list:
            return [ch for n in cluster.nodes.values() for d in n.disks.values() for ch in d.chunks.values()]

        live0 = sum(ch.live for ch in chunks_now())
        if live0 != n_old * model_of.stored_bytes(size, config):
            errors.append(f"the loaded generation's records are {live0} bytes, the model "
                          f"{n_old * model_of.stored_bytes(size, config)}")
        bn, sch = registry("blobnode"), registry("scheduler")
        base = {"punched": bn.counter("punched_bytes").value, "punch_failed": bn.counter("punch_failed").value,
                "holes": bn.counter("hole_bytes").value, "released": bn.counter("released_bytes").value,
                "extents": bn.counter("extents_dropped").value,
                "garbage": bn.counter("compact_bytes", {"kind": "garbage"}).value,
                "dead_held": sum(ch.held - ch.live for ch in chunks_now()),
                "reclaimed": bn.counter("compact_bytes", {"kind": "reclaimed"}).value,
                "copied": bn.counter("compact_bytes", {"kind": "copied"}).value,
                "compactions": bn.counter("compact_total").value,
                "applied": sch.counter("delete_blobs", {"result": "ok"}).value}

        # -- the writers expire it to its end; a prober; compaction under them ---
        cursor, acked, written = [0], [], [[] for _ in range(streams)]
        done = threading.Event()

        def writer(s: int) -> None:
            c = AccessClient([daemon.addr])
            q = 0
            while True:
                written[s].append((s + 1, q, c.put(payload(s + 1, q))))
                q += 1
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                if i >= n_old:
                    return
                c.delete(old[i])
                with lock:
                    acked.append((time.monotonic(), i))

        probes = {"deleted": 0, "live": 0}

        def prober(_: int) -> None:
            c = AccessClient([daemon.addr])
            rng = np.random.default_rng([seed, 0x9B0])
            while not done.wait(1.0 / params["probes_per_s"]):
                with lock:
                    gone = [i for t, i in acked if t <= time.monotonic() - params["apply_within_s"]]
                    first_live = cursor[0] + params["live_margin"]
                if gone:
                    i = gone[int(rng.integers(len(gone)))]
                    try:
                        c.get(old[i])
                        errors.append(f"old object {i} answered bytes {params['apply_within_s']} s after its DELETE")
                    except AccessError:
                        probes["deleted"] += 1
                if first_live < n_old:
                    i = int(rng.integers(first_live, n_old))
                    if c.get(old[i]) != payload(0, i):
                        errors.append(f"live old object {i} differs beside punched records")
                    probes["live"] += 1

        def compact_a_quarter_holes() -> None:
            for nid in compact_nodes:
                for disk in cluster.nodes[nid].disks.values():
                    for ch in list(disk.chunks.values()):
                        if ch.holes >= 16 << 20 and ch.holes >= 0.25 * ch.used:
                            ch.compact()

        def compactor(_: int) -> None:
            while not done.wait(2.0):
                compact_a_quarter_holes()

        t0 = time.monotonic()
        side = ThreadPoolExecutor(2)
        side.submit(guarded, prober, 0)
        side.submit(guarded, compactor, 0)
        every(streams, writer)
        out["expire_s"] = time.monotonic() - t0
        # -- quiescence: the topic empty, every blob applied -----------------------
        blobs = -(-size // config["max_blob_size"]) * n_old
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and (
                cluster.proxy.delete_backlog()
                or sch.counter("delete_blobs", {"result": "ok"}).value - base["applied"] < blobs):
            time.sleep(0.05)
        out["quiescent_s"] = time.monotonic() - t0 - out["expire_s"]
        done.set()
        side.shutdown(wait=True)
        compact_a_quarter_holes()  # what the last sweep left: once more, nothing written beside it
        out["topic_backlog"] = cluster.proxy.delete_backlog()
        out["blobs_applied"] = sch.counter("delete_blobs", {"result": "ok"}).value - base["applied"]
        out["blobs_partial"] = sch.counter("delete_blobs", {"result": "partial"}).value
        out["probes"] = probes
        if out["topic_backlog"] or out["blobs_applied"] != blobs:
            errors.append(f"not quiescent: backlog {out['topic_backlog']}, {out['blobs_applied']} of {blobs} blobs applied")

        # -- every deleted object is not-found ---------------------------------------
        not_found = [0] * streams

        def check_old(s: int) -> None:
            c = AccessClient([daemon.addr])
            for i in range(s, n_old, streams):
                try:
                    c.get(old[i])
                    errors.append(f"old object {i} answered bytes after quiescence")
                except AccessError:
                    not_found[s] += 1

        every(streams, check_old)
        out["deleted_not_found"] = sum(not_found)

        # -- every object written meanwhile: its bytes and every shard of it --------
        objs = [w for s in written for w in s]
        mode = model_of.mode_of(size, config)
        counted = {"objects_equal": 0, "shards_equal": 0, "shards_differing": 0, "shards_missing": 0,
                   "shards_in_compacted_chunks": 0, "shards_in_uncompacted_chunks": 0}

        def check_written(s: int) -> None:
            c = AccessClient([daemon.addr])
            for a, b, loc in objs[s::streams]:
                want = payload(a, b)
                equal = c.get(loc) == want
                off, tally = 0, dict.fromkeys(counted, 0)
                tally["objects_equal"] = int(equal)
                for blob in loc.blobs:
                    ref = reference.encode(want[off: off + blob.size], mode, config["code"])
                    off += blob.size
                    for u in cluster.cm.get_volume(blob.vid).units:
                        node = cluster.nodes[u.node_id]
                        try:
                            got = node.get_shard(u.vuid, blob.bid)
                        except Exception:
                            tally["shards_missing"] += 1
                            continue
                        tally["shards_equal" if got == ref[u.index].tobytes() else "shards_differing"] += 1
                        tally["shards_in_compacted_chunks" if node._chunk(u.vuid).gen
                              else "shards_in_uncompacted_chunks"] += 1
                with lock:
                    for k, v in tally.items():
                        counted[k] += v

        t0 = time.monotonic()
        every(streams, check_written)
        out["verify_s"] = time.monotonic() - t0
        out.update(counted, objects_written=len(objs))
        total = mode["N"] + mode["M"] + mode["L"]
        if counted["objects_equal"] != len(objs) or counted["shards_differing"] or \
                counted["shards_equal"] + counted["shards_missing"] != len(objs) * total * (blobs // n_old) or \
                counted["shards_missing"] > len(objs) * (blobs // n_old) * (total - mode["put_quorum"]):
            errors.append(f"written objects: {counted} of {len(objs)}")
        if compact_nodes and not (counted["shards_in_compacted_chunks"] and counted["shards_in_uncompacted_chunks"]):
            errors.append("the written shards do not lie in compacted AND uncompacted chunks")

        # -- what the disks hold, and where every dead byte went ----------------------
        chunks = chunks_now()
        out["disks_hold"] = sum(ch.live for ch in chunks)  # bytes of live records
        out["fs_holds"] = sum(d["held"] for n in cluster.nodes.values() for d in n.stats()["disks"])
        out["datafile_bytes"] = sum(d["used"] for n in cluster.nodes.values() for d in n.stats()["disks"])
        out["model_holds"] = (len(objs) * total * (blobs // n_old) - counted["shards_missing"]) * \
            model_of.record_bytes(reference.shard_size(config["max_blob_size"], mode["N"],
                                                       config["code"]["min_shard_size"]), config["record_framing"])
        out["dead_bytes_held"] = out["fs_holds"] - out["disks_hold"]
        out["hole_bytes"] = bn.counter("hole_bytes").value - base["holes"]
        out["released_bytes"] = bn.counter("released_bytes").value - base["released"]
        out["extents_dropped"] = bn.counter("extents_dropped").value - base["extents"]
        out["punched_bytes"] = bn.counter("punched_bytes").value - base["punched"]
        out["punch_failed"] = bn.counter("punch_failed").value - base["punch_failed"]
        out["compactions"] = bn.counter("compact_total").value - base["compactions"]
        out["compact_copied"] = bn.counter("compact_bytes", {"kind": "copied"}).value - base["copied"]
        out["compact_reclaimed"] = bn.counter("compact_bytes", {"kind": "reclaimed"}).value - base["reclaimed"]
        out["chunks_compacted"] = sum(1 for ch in chunks if ch.gen)
        out["chunks"] = len(chunks)
        # a volume whose chunks fill in the middle of a stripe is retired and the blob
        # written again, under the same bid, on the next (Access._write_blob): the shards
        # the full volume had already taken stay there, live and nobody's. Known by
        # their bid: a written object holds it on ANOTHER volume.
        placed = {(b.vid, b.bid) for _, _, loc in objs for b in loc.blobs}
        bids = {bid for _, bid in placed}
        orphans = [(parse_vuid(m.vuid)[0], m.bid, m.size) for ch in chunks for m in ch.list_shards()
                   if (parse_vuid(m.vuid)[0], m.bid) not in placed]
        out["orphans_of_retired_volumes"] = len(orphans)
        out["orphan_bytes"] = sum(model_of.record_bytes(sz, config["record_framing"]) for _, _, sz in orphans)
        if any(bid not in bids for _, bid, _ in orphans):
            errors.append(f"{sum(1 for _, b, _ in orphans if b not in bids)} live records are no written object's")
        if size % config["max_blob_size"] == 0 and out["disks_hold"] - out["orphan_bytes"] != out["model_holds"]:
            errors.append(f"the disks hold {out['disks_hold']} bytes ({out['orphan_bytes']} of them orphans of "
                          f"retired volumes), the model {out['model_holds']}")
        # dead bytes ever made (records made holes, and the copies a compaction wrote of
        # records that died under it) went back to the filesystem or are still held
        out["compact_garbage"] = bn.counter("compact_bytes", {"kind": "garbage"}).value - base["garbage"]
        if out["hole_bytes"] + out["compact_garbage"] != out["released_bytes"] + out["dead_bytes_held"] - base["dead_held"]:
            errors.append(f"holes made {out['hole_bytes']} + garbage {out['compact_garbage']} != given back "
                          f"{out['released_bytes']} + dead and still held {out['dead_bytes_held']} "
                          f"(- {base['dead_held']} at the start)")
        if out["dead_bytes_held"] > (len(chunks) * type(chunks[0]).EXTENT_SIZE if out["punch_failed"] else 0):
            errors.append(f"{out['dead_bytes_held']} dead bytes are still held in {len(chunks)} chunks")
        if out["hole_bytes"] != n_old * model_of.stored_bytes(size, config):
            errors.append(f"holes made {out['hole_bytes']} != the old generation's "
                          f"{n_old * model_of.stored_bytes(size, config)} stored bytes")
        # a filesystem either takes PUNCH_HOLE or refuses it (a 9p root: EOPNOTSUPP)
        if out["punched_bytes"] not in (0, out["hole_bytes"]) or bool(out["punch_failed"]) == bool(out["punched_bytes"]):
            errors.append(f"punched {out['punched_bytes']} of {out['hole_bytes']} with {out['punch_failed']} refused")
        if compact_nodes and not out["compactions"]:
            errors.append("no chunk was compacted under the writers")
    finally:
        daemon.stop()
    out["errors"] = errors[:10]
    out["ok"] = not errors
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True, help="an empty directory for the daemon's data")
    ap.add_argument("--jax-platform", default=None)
    ap.add_argument("--objects", type=int, default=None, help="loaded objects (default: the traffic file's)")
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--timeout", type=float, default=300.0, help="seconds to wait for quiescence")
    ap.add_argument("--compact-nodes", default="1,2,3",
                    help="nodes whose chunks are compacted under the writers once a quarter is holes; "
                         "'' leaves compaction to the rule as committed")
    args = ap.parse_args(argv)
    out = drive(args.root, args.jax_platform, args.objects, args.seed, args.timeout,
                [int(n) for n in args.compact_nodes.split(",") if n])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
