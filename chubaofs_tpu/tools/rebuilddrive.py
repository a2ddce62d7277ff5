"""Rebuild drive: load, lose a node (or single disks of nodes that stay up),
declare the disks broken, let the scheduler rebuild them under the cell's read
traffic, then compare EVERY rebuilt shard, data and parity, with the plain
reference's stripe and the unit map with the placement guarantees.

    python -m chubaofs_tpu.tools.rebuilddrive --root /tmp/rebuild      # needs the TPU
    python -m chubaofs_tpu.tools.rebuilddrive --root /tmp/rebuild --jax-platform cpu --objects 6
    python -m chubaofs_tpu.tools.rebuilddrive --root /tmp/lrc --config az2-ec16p20l2-localrepair \
        [--disks 1:0,2:0] [--jax-platform cpu --objects 6]

Layout, the node that is lost, the objects and the reader streams come from the
benchmark's configuration and traffic file of this deployment
(benchmark/configs/az1-ec12p4-rebuild.json, benchmark/traffic/get16m-rebuild.json:
the timed cell az1.get16m-rebuild), so this drive and the cell state one
deployment; `--objects` and `--node` override two of them (node 7 holds PARITY
units of the first volume, node 1 data units of both). The daemon boots in this
process exactly as `chubaofs-tpu -c blobstore.json` boots it (cmd.start_role);
clients speak HTTP to its gateway: PUTs, the readers' GETs (every body
compared), GET /admin/disks and the operator's POST /admin/disk/set. The node is
closed and dropped from the routing table under the daemon's runner lock, as
chaos/scheduler.py `_kill` does. Nothing is timed for a result: the rebuild runs
to its end, outside any window. One JSON line on stdout; exit 1 and
`"ok": false` if a body or a rebuilt shard differs, a placement guarantee is
broken, or a disk is not DROPPED.

`--config az2-ec16p20l2-localrepair` (the timed cell az2.get16m-localrepair)
takes layout, objects and the disks to declare from that configuration and its
traffic file (`declare_broken_disks`: {node, nth}, the nth disk of the node in
GET /admin/disks order; `--disks node:nth,...` overrides them). No engine is
closed: the nodes stay routed and the program's own refusal of a BROKEN disk's
I/O is what is under test. Every rebuilt shard of an LRC volume is also
compared with benchmark/reference_local_repair.py's row, solved from the stored
shards of the unit's OWN AZ's local stripe (in blocks of stripes); the line
also carries `placement_violations` with the same-AZ rule, the bytes the
rebuild read across the AZ boundary (`rebuild_cross_az_bytes`, must be 0 where
every AZ stripe has one hole), `rebuild_local_jobs` beside
`rebuild_decode_jobs`, the fall-backs, and what the broken disks' blobnodes
refused."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import struct
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                     "benchmark")


def _bench_module(name: str):
    """A module of benchmark/ by path (reference_rebuild imports its siblings)."""
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location("benchmark_" + name,
                                                      os.path.join(BENCH, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path.remove(BENCH)


LOCAL_BLOCK = 64  # stripes solved at once by the local reference (18 x 64 x 256 KiB = 300 MB)


def _cell_files(config_name: str) -> tuple[dict, dict]:
    """(configuration, traffic parameters) of the benchmark cell that runs
    ``config_name`` (BENCHMARK.json names both files)."""
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == config_name)
    cell = next(w for w in bench["workloads"] if w["config"] == config_name)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        return config, json.load(f)["params"]


def drive(root: str, platform: str | None, objects: int | None, node: int | None,
          seed: int, timeout_s: float, config_name: str = "az1-ec12p4-rebuild",
          disks_arg: list[dict] | None = None) -> dict:
    from chubaofs_tpu import cmd
    from chubaofs_tpu.blobstore.gateway import AccessClient
    from chubaofs_tpu.codec.codemode import CodeMode
    from chubaofs_tpu.ops import device
    from chubaofs_tpu.rpc.client import RPCClient
    from chubaofs_tpu.utils.exporter import registry

    reference = _bench_module("reference")
    reference_rebuild = _bench_module("reference_rebuild")
    reference_local = _bench_module("reference_local_repair")
    config, params = _cell_files(config_name)
    lay = config["layout"]
    # single disks of nodes that stay up, or whole nodes closed
    want_disks = disks_arg or ([] if node else params.get("declare_broken_disks", []))
    lost = [] if want_disks else ([node] if node else config["failure"]["nodes"])
    n_objects = objects or params["objects"]
    size, streams = params["object_bytes"], params["streams"]
    device.request_platform(platform)
    device.enable_compile_cache()
    cfg = {"role": "blobstore", "root": root, "listen": "127.0.0.1:0",
           "nodes": lay["nodes"], "disksPerNode": lay["disks_per_node"], "azs": lay["azs"]}
    if platform:
        cfg["jaxPlatform"] = platform
    daemon = cmd.start_role(cfg)
    out: dict = {"boot": dict(daemon.boot_info), "config": config_name, "lost_nodes": lost,
                 "lost_disks": want_disks, "objects": n_objects, "object_bytes": size, "seed": seed}
    try:
        cluster = daemon.runner.handles["cluster"]
        bases = [np.random.default_rng([seed, 0x4EB, i]).bytes(size) for i in range(4)]

        def payload(i: int) -> bytes:
            return struct.pack("<QQ", seed, i) + bases[i % 4][16:]

        # -- load, as the cell's set-up does: `load_streams` clients ------------
        tokens: list = [None] * n_objects
        nxt, lock, errors = iter(range(n_objects)), threading.Lock(), []

        def loader() -> None:
            c = AccessClient([daemon.addr])
            while True:
                with lock:
                    i = next(nxt, None)
                if i is None:
                    return
                tokens[i] = c.put(payload(i))

        def guarded(target, *args) -> None:
            try:
                target(*args)
            except Exception as e:  # a failed client is the drive's failure
                errors.append(f"{type(e).__name__}: {e}")

        t0 = time.monotonic()
        with ThreadPoolExecutor(params["load_streams"], thread_name_prefix="load") as pool:
            for _ in range(params["load_streams"]):
                pool.submit(guarded, loader)
        out["load_s"] = time.monotonic() - t0
        admin = RPCClient([daemon.addr])
        listed = admin.get("/admin/disks")
        disks = [d["disk_id"] for d in listed if d["node_id"] in lost] + [
            [d["disk_id"] for d in listed if d["node_id"] == w["node"]][w["nth"]] for w in want_disks]
        held = [(v.vid, u.index) for v in cluster.cm.volumes.values() for u in v.units
                if u.disk_id in disks]
        was = {(v.vid, u.index): u.disk_id for v in cluster.cm.volumes.values() for u in v.units
               if u.disk_id in disks}
        daemon.runner.call_with("cluster", lambda c: [c.nodes.pop(n).close() for n in lost])
        reg, bn = registry("scheduler"), registry("blobnode")
        series = ("repaired_shards", "rebuild_units_committed", "rebuild_decode_jobs", "rebuild_local_jobs",
                  "rebuild_local_fallbacks", "rebuild_cross_az_bytes")
        base = {n: reg.counter(n).value for n in series}
        base.update({k: reg.counter("rebuild_bytes", {"kind": k}).value for k in ("read", "written")})
        refused0 = bn.counter("io_refused", {"reason": "disk_broken"}).value

        # -- the damaged state reads back before the rebuild ---------------------
        c0 = AccessClient([daemon.addr])
        for i in range(min(4, n_objects)):
            if c0.get(tokens[i]) != payload(i):
                errors.append(f"object {i} differs before the rebuild")
        compiled = registry("codec").counter("compile_total").value

        # -- readers, the declaration, the rebuild to its end --------------------
        stop, gets, differing = threading.Event(), [0] * streams, [0] * streams

        def reader(s: int) -> None:
            c = AccessClient([daemon.addr])
            rng = np.random.default_rng([seed, 0x6E7, s])
            while not stop.is_set():
                i = int(rng.integers(n_objects))
                if c.get(tokens[i]) != payload(i):
                    differing[s] += 1
                gets[s] += 1

        with ThreadPoolExecutor(streams, thread_name_prefix="reader") as pool:
            for s_ in range(streams):
                pool.submit(guarded, reader, s_)
            t_declared = time.monotonic()
            try:
                out["declared"] = [admin.post(f"/admin/disk/set?disk_id={d}&status=broken")
                                   for d in disks]
                deadline = t_declared + timeout_s
                while time.monotonic() < deadline and any(
                        cluster.cm.disk_status(d) != "dropped" for d in disks):
                    time.sleep(0.2)
                out["rebuild_s"] = time.monotonic() - t_declared
                time.sleep(1.0)  # the readers meet the healed state too
            finally:
                stop.set()
        out["disk_status"] = {d: cluster.cm.disk_status(d) for d in disks}
        out["gets_during_rebuild"], out["bodies_differing"] = sum(gets), sum(differing)
        # no closed-set warm-up here (that is the timed cell's harness): programs
        # the rebuild's batch counts compile are only reported
        out["compiles_during_rebuild"] = registry("codec").counter("compile_total").value - compiled
        grown = {n: reg.counter(n).value - base[n] for n in series}
        out["rebuilt_shards"], out["units_committed"] = grown.pop("repaired_shards"), grown.pop(
            "rebuild_units_committed")
        out.update(grown)
        out["rebuild_bytes"] = {k: reg.counter("rebuild_bytes", {"kind": k}).value - base[k]
                                for k in ("read", "written")}
        out["io_refused_disk_broken"] = bn.counter("io_refused", {"reason": "disk_broken"}).value - refused0
        out["read_plans"] = {p: registry("access").counter("read_plan_total", {"plan": p}).value
                             for p in ("direct", "one_round", "two_round")}

        # -- every rebuilt shard against the reference stripe; the placement -------
        compared = bad = 0
        positions = {}
        for vid, pos in held:
            positions.setdefault(vid, []).append(pos)
        for i, token in enumerate(tokens):
            loc, data, off = token, payload(i), 0
            mode = config["modes"][CodeMode(loc.code_mode).name]
            for b in loc.blobs:
                want_pos = positions.get(b.vid, [])
                blob = data[off: off + b.size]
                off += b.size
                if not want_pos:
                    continue
                stripe = (reference.encode(blob, mode, config["code"]) if max(want_pos) >= mode["N"]
                          else reference.split(blob, mode["N"], config["code"]["min_shard_size"]))
                for pos in want_pos:
                    unit = cluster.cm.get_volume(b.vid).units[pos]
                    n = cluster.nodes.get(unit.node_id)
                    compared += 1
                    try:
                        if n is None or n.get_shard(unit.vuid, b.bid) != stripe[pos].tobytes():
                            bad += 1
                    except Exception:
                        bad += 1
        out["shards_compared"], out["shards_differing"] = compared, bad
        out["positions_rebuilt"] = sorted(held)

        # -- an LRC volume's rebuilt rows against the local reference: each solved from
        # the STORED shards of the unit's own AZ's local stripe, a block of stripes at once
        local_compared = local_bad = 0
        bids: dict[int, list[int]] = {}
        for token in tokens:
            for b in token.blobs:
                bids.setdefault(b.vid, []).append(b.bid)
        for vid, pos in sorted(held):
            vol = cluster.cm.get_volume(vid)
            mode = config["modes"][CodeMode(vol.code_mode).name]
            if not mode["L"] or pos >= mode["N"] + mode["M"]:
                continue
            own = reference_local.local_stripe(mode, reference_local.az_of(mode, pos))
            mine = bids.get(vid, [])
            for lo in range(0, len(mine), LOCAL_BLOCK):
                block = mine[lo: lo + LOCAL_BLOCK]
                stored: list = [None] * (mode["N"] + mode["M"] + mode["L"])
                try:
                    for g in own:
                        u = vol.units[g]
                        stored[g] = b"".join(cluster.nodes[u.node_id].get_shard(u.vuid, bid) for bid in block)
                    got, stored[pos] = stored[pos], None
                    same = reference_local.local_rebuilt_row(stored, pos, mode, config["code"]) == got
                except Exception as e:
                    errors.append(f"local reference {vid}/{pos}: {type(e).__name__}: {e}")
                    same = False
                local_compared += len(block)
                local_bad += 0 if same else len(block)
        out["shards_compared_local_reference"], out["shards_differing_local_reference"] = local_compared, local_bad
        out["placement_violations"] = reference_rebuild.placement_violations(
            {v.vid: [u.disk_id for u in v.units] for v in cluster.cm.volumes.values()},
            {d.disk_id: d.status for d in cluster.cm.disks.values()})
        az = {d.disk_id: d.az for d in cluster.cm.disks.values()}
        for (vid, pos), old in sorted(was.items()):
            new = cluster.cm.get_volume(vid).units[pos].disk_id
            if az[new] != az[old]:
                out["placement_violations"].append(
                    f"volume {vid} position {pos} moved from AZ {az[old]} (disk {old}) to AZ {az[new]} (disk {new})")
        for i in range(n_objects):
            if c0.get(tokens[i]) != payload(i):
                errors.append(f"object {i} differs after the rebuild")
        out["errors"] = errors[:5]
        out["ok"] = bool(
            not errors and not bad and compared > 0 and not local_bad
            and out["rebuilt_shards"] >= compared and not out["bodies_differing"]
            and not out["placement_violations"]
            and all(s == "dropped" for s in out["disk_status"].values()))
    finally:
        daemon.stop()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="lose a node or single disks, declare them, rebuild them under reads, "
                    "compare every rebuilt shard")
    p.add_argument("--root", required=True, help="state directory (made, must be empty)")
    p.add_argument("--jax-platform", default="",
                   help="pin the codec to a platform (cpu for the sandbox); default JAX's own")
    p.add_argument("--objects", type=int, default=0, help="default: the traffic file's")
    p.add_argument("--node", type=int, default=0, help="default: the configuration's failure.nodes")
    p.add_argument("--config", default="az1-ec12p4-rebuild",
                   help="a benchmark configuration with a rebuild cell (also: az2-ec16p20l2-localrepair)")
    p.add_argument("--disks", default="",
                   help="node:nth,... single disks to declare, their nodes staying up "
                        "(default: the traffic file's declare_broken_disks)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=900.0, help="seconds the rebuild may take")
    args = p.parse_args(argv)
    os.makedirs(args.root, exist_ok=True)
    if os.listdir(args.root):
        print(f"--root {args.root} is not empty", file=sys.stderr)
        return 2
    disks = [dict(zip(("node", "nth"), map(int, d.split(":")))) for d in args.disks.split(",") if d]
    out = drive(args.root, args.jax_platform or None, args.objects or None, args.node or None,
                args.seed, args.timeout, args.config, disks)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
