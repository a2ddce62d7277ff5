"""Node-loss rebuild drive: load, lose a node, declare its disks broken, let the
scheduler rebuild them under the cell's read traffic, then compare EVERY
rebuilt shard, data and parity, with the plain reference's stripe and the unit
map with the placement guarantees.

    python -m chubaofs_tpu.tools.rebuilddrive --root /tmp/rebuild      # needs the TPU
    python -m chubaofs_tpu.tools.rebuilddrive --root /tmp/rebuild --jax-platform cpu --objects 6

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
broken, or a disk is not DROPPED."""

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


def drive(root: str, platform: str | None, objects: int | None, node: int | None,
          seed: int, timeout_s: float) -> dict:
    from chubaofs_tpu import cmd
    from chubaofs_tpu.blobstore.gateway import AccessClient
    from chubaofs_tpu.codec.codemode import CodeMode
    from chubaofs_tpu.ops import device
    from chubaofs_tpu.rpc.client import RPCClient
    from chubaofs_tpu.utils.exporter import registry

    reference = _bench_module("reference")
    reference_rebuild = _bench_module("reference_rebuild")
    with open(os.path.join(BENCH, "configs", "az1-ec12p4-rebuild.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "get16m-rebuild.json")) as f:
        params = json.load(f)["params"]
    lay = config["layout"]
    lost = [node] if node else config["failure"]["nodes"]
    n_objects = objects or params["objects"]
    size, streams = params["object_bytes"], params["streams"]
    device.request_platform(platform)
    device.enable_compile_cache()
    cfg = {"role": "blobstore", "root": root, "listen": "127.0.0.1:0",
           "nodes": lay["nodes"], "disksPerNode": lay["disks_per_node"], "azs": lay["azs"]}
    if platform:
        cfg["jaxPlatform"] = platform
    daemon = cmd.start_role(cfg)
    out: dict = {"boot": dict(daemon.boot_info), "lost_nodes": lost, "objects": n_objects,
                 "object_bytes": size, "seed": seed}
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
        held = [(v.vid, u.index) for v in cluster.cm.volumes.values() for u in v.units
                if u.node_id in lost]
        daemon.runner.call_with("cluster", lambda c: [c.nodes.pop(n).close() for n in lost])

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
            admin = RPCClient([daemon.addr])
            disks = [d["disk_id"] for d in admin.get("/admin/disks") if d["node_id"] in lost]
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
        reg = registry("scheduler")
        out["rebuilt_shards"] = reg.counter("repaired_shards").value
        out["units_committed"] = reg.counter("rebuild_units_committed").value
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
        out["placement_violations"] = reference_rebuild.placement_violations(
            {v.vid: [u.disk_id for u in v.units] for v in cluster.cm.volumes.values()},
            {d.disk_id: d.status for d in cluster.cm.disks.values()})
        for i in range(n_objects):
            if c0.get(tokens[i]) != payload(i):
                errors.append(f"object {i} differs after the rebuild")
        out["errors"] = errors[:5]
        out["ok"] = bool(
            not errors and not bad and compared > 0
            and out["rebuilt_shards"] >= compared and not out["bodies_differing"]
            and not out["placement_violations"]
            and all(s == "dropped" for s in out["disk_status"].values()))
    finally:
        daemon.stop()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="lose a node, declare it, rebuild it under reads, compare every rebuilt shard")
    p.add_argument("--root", required=True, help="state directory (made, must be empty)")
    p.add_argument("--jax-platform", default="",
                   help="pin the codec to a platform (cpu for the sandbox); default JAX's own")
    p.add_argument("--objects", type=int, default=0, help="default: the traffic file's")
    p.add_argument("--node", type=int, default=0, help="default: the configuration's failure.nodes")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=900.0, help="seconds the rebuild may take")
    args = p.parse_args(argv)
    os.makedirs(args.root, exist_ok=True)
    if os.listdir(args.root):
        print(f"--root {args.root} is not empty", file=sys.stderr)
        return 2
    out = drive(args.root, args.jax_platform or None, args.objects or None, args.node or None,
                args.seed, args.timeout)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
