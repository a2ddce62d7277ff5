"""Two-AZ served-path drive: PUT through the gateway, then GET healthy, with
one node down and with a whole AZ down, every body compared byte for byte.

    python -m chubaofs_tpu.tools.azdrive --root /tmp/azdrive      # needs the TPU
    python -m chubaofs_tpu.tools.azdrive --root /tmp/azdrive --jax-platform cpu

The deployment is upstream's two-AZ production table (EC6P10L2 up to
1 MiB, EC16P20L2 above) on 12 nodes x 4 disks: a 64 KiB and a 1 MiB object
take EC6P10L2, a 16 MiB object takes EC16P20L2 as four blobs of 38 shards.
A whole AZ down leaves EC16P20L2 its other AZ's 8 data + 10 global-parity
shards (>= 16) and EC6P10L2 3 + 5 (>= 6): every object still reads back.
Layout, the AZ that goes dark with its nodes, and the repair switches held
are read from the benchmark's configuration of that outage
(benchmark/configs/az2-ec16p20l2-azdown.json, the cell az2.get16m-azdown), so
this drive and the timed cell state one deployment.

The blobstore daemon boots in this process exactly as `chubaofs-tpu -c
blobstore.json` boots it (cmd.start_role), so this process owns the chip;
clients speak HTTP to its gateway. A node that is down is dropped from the
routing table under the daemon's runner lock (all a read sees of
chaos/scheduler.py `_kill`) and routed again after its step: each object is
read once with the node of its own first data shard down, then all with one
whole AZ down. Shard and disk repair are switched off so a degraded GET
decodes instead of finding the loss already healed. One JSON line on stdout;
exit 1 and `"ok": false` if any body differs or a degraded GET decoded nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                      "benchmark", "configs", "az2-ec16p20l2-azdown.json")


def drive(root: str, platform: str | None, sizes: list[int], seed: int) -> dict:
    from chubaofs_tpu import cmd
    from chubaofs_tpu.blobstore.gateway import AccessClient
    from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
    from chubaofs_tpu.ops import device
    from chubaofs_tpu.utils.exporter import registry

    with open(CONFIG) as f:
        config = json.load(f)
    lay, failure = config["layout"], config["failure"]
    device.request_platform(platform)
    device.enable_compile_cache()
    cfg = {"role": "blobstore", "root": root, "listen": "127.0.0.1:0",
           "nodes": lay["nodes"], "disksPerNode": lay["disks_per_node"], "azs": lay["azs"]}
    if platform:
        cfg["jaxPlatform"] = platform
    daemon = cmd.start_role(cfg)
    out: dict = {"boot": dict(daemon.boot_info), "objects": [], "steps": []}
    try:
        cluster = daemon.runner.handles["cluster"]
        for name in config["task_switches_off"]:
            cluster.scheduler.switches.set(name, False)
        client = AccessClient([daemon.addr])
        client.rpc.timeout = 600.0  # a cold daemon compiles inside the first PUTs

        decoded = registry("access").counter("read_bytes", {"kind": "decoded"})
        objects = []
        for i, size in enumerate(sizes):
            data = np.random.default_rng([seed, i]).bytes(size)
            loc = client.put(data)
            t = get_tactic(loc.code_mode)
            objects.append((loc, data))
            out["objects"].append({"bytes": size, "mode": CodeMode(loc.code_mode).name,
                                   "blobs": len(loc.blobs), "shards_a_blob": t.total})

        def get(step: str, down: list[int], some) -> None:
            """GET ``some`` objects with nodes ``down`` unreachable: dropped from
            the routing table (all a read sees of a dead host), routed again after."""
            gone: dict = {}
            daemon.runner.call_with(
                "cluster", lambda c: gone.update({n: c.nodes.pop(n) for n in down}))
            try:
                before, t0 = decoded.value, time.perf_counter()
                differing = sum(1 for loc, data in some if client.get(loc) != data)
                out["steps"].append({
                    "step": step, "nodes_down": down, "object_bytes": [loc.size for loc, _ in some],
                    "differing": differing, "decoded_bytes": decoded.value - before,
                    "seconds": round(time.perf_counter() - t0, 3)})
            finally:
                daemon.runner.call_with("cluster", lambda c: c.nodes.update(gone))

        get("healthy", [], objects)
        for loc, data in objects:  # the node of the object's first data shard
            unit0 = cluster.cm.get_volume(loc.blobs[0].vid).units[0]
            get("node_down", [unit0.node_id], [(loc, data)])
        az_nodes = sorted({d.node_id for d in cluster.cm.disks.values() if d.az == failure["az_down"]})
        if az_nodes != failure["nodes"]:
            raise SystemExit(f"{CONFIG}: AZ {failure['az_down']} is nodes {az_nodes} "
                             f"in this cluster, the file says {failure['nodes']}")
        get("az_down", failure["nodes"], objects)
    finally:
        daemon.stop()
    out["ok"] = (len(out["steps"]) == len(sizes) + 2
                 and all(s["differing"] == 0 for s in out["steps"])
                 and all(s["decoded_bytes"] > 0 for s in out["steps"] if s["nodes_down"]))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="azdrive", description="PUT / GET / node-down GET / AZ-down GET through a "
                                    "two-AZ blobstore daemon, byte for byte")
    p.add_argument("--root", required=True, help="state directory (made, must be empty)")
    p.add_argument("--jax-platform", default="",
                   help="platform of the daemon (cpu, tpu); default JAX's own")
    p.add_argument("--sizes", default="65536,1048576,16777216",
                   help="object sizes in bytes, comma separated")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    out = drive(args.root, args.jax_platform or None,
                [int(s) for s in args.sizes.split(",")], args.seed)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
