"""The one file that knows the program's API: boots the system under test in
this process and reads what the harness needs from it. Everything else under
benchmark/ sees a Deployment, never chubaofs_tpu.

Taken from the program: the daemon (chubaofs_tpu.cmd.BlobstoreDaemon started as
cmd.main starts it), its /metrics text, its shard inventory, its task switches,
and its CodecService for warming the cell's closed set of compiled programs."""

from __future__ import annotations

import json
import os

import numpy as np


def parse_metrics(text: str) -> dict[str, float]:
    """Prometheus text -> {'name{labels}': value}."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


class Deployment:
    def __init__(self, config: dict, root: str, platform: str):
        from chubaofs_tpu import cmd
        from chubaofs_tpu.ops import device

        self.config = config
        device.request_platform(platform)
        self.cache_dir = device.enable_compile_cache()
        lay = config["layout"]
        self.daemon = cmd.start_role({
            "role": "blobstore", "root": root, "listen": "127.0.0.1:0",
            "nodes": lay["nodes"], "disksPerNode": lay["disks_per_node"],
            "azs": lay["azs"], "jaxPlatform": platform})
        self.addr = self.daemon.addr
        self.boot_info = dict(self.daemon.boot_info)
        self.cluster = self.daemon.runner.handles["cluster"]
        self._check_matches_config()
        self.switch_off(config.get("task_switches_off", []))

    def _check_matches_config(self) -> None:
        """The deployment the program built must be the one the file states:
        same policy table, same code geometry, same blob size, no cache plane."""
        from chubaofs_tpu.codec.codemode import get_tactic

        access = self.cluster.access
        want = [(p["mode"], p["min_size"], p["max_size"]) for p in self.config["policies"]]
        got = [(p.mode.name, p.min_size, min(p.max_size, 1 << 62)) for p in access.policies]
        want = [(m, lo, (1 << 62) if hi is None else hi) for m, lo, hi in want]
        if got != want:
            raise SystemExit(f"policy table differs from the configuration: {got} != {want}")
        for name, mode in self.config["modes"].items():
            t = get_tactic(name)
            have = {"N": t.N, "M": t.M, "L": t.L, "az_count": t.az_count,
                    "put_quorum": t.put_quorum}
            if have != mode:
                raise SystemExit(f"mode {name} differs from the configuration: {have} != {mode}")
        if access.max_blob_size != self.config["max_blob_size"]:
            raise SystemExit(f"blob size {access.max_blob_size} != configuration")
        if (self.cluster.cache is not None) != self.config["cache_plane"]:
            raise SystemExit("cache plane differs from the configuration")
        if len(self.cluster.cm.disks) != self.config["layout"]["nodes"] * self.config["layout"]["disks_per_node"]:
            raise SystemExit("disk count differs from the configuration")

    # -- what the program counts ------------------------------------------

    def counters(self) -> dict[str, float]:
        from chubaofs_tpu.utils import exporter

        return parse_metrics(exporter.render_all())

    def stored_bytes(self) -> int:
        """Bytes the blobnodes hold in chunk files (headers and crc framing
        included)."""
        return sum(d["used"] for n in self.cluster.nodes.values()
                   for d in n.stats()["disks"])

    def device(self) -> dict:
        import jax

        devs = jax.devices()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": int(peak)}

    # -- the closed set of compiled programs --------------------------------

    def _exact_batches(self, submit, counts) -> list[int]:
        """Drive the daemon's CodecService so that it drains one batch of
        exactly b jobs for each b: with max_batch = b the drain returns as soon
        as b jobs are queued. Both attributes are restored before any traffic.
        Returns the counts that did NOT drain as one batch."""
        codec = self.cluster.codec
        keep = (codec.max_batch, codec.max_wait)
        missed = []
        codec.max_wait = 5.0
        try:
            for b in counts:
                codec.max_batch = b
                before = codec.stats_snapshot()
                for f in [submit() for _ in range(b)]:
                    f.result()
                after = codec.stats_snapshot()
                if (after["batches"] - before["batches"], after["jobs"] - before["jobs"]) != (1, b):
                    missed.append(b)
        finally:
            codec.max_batch, codec.max_wait = keep
        return missed

    def _warm_shapes(self, shapes: dict, max_count: int, named: dict) -> dict:
        """Drive every shape ``label -> (call, rows, k)`` with (rows, k) seeded
        bytes (any will do: only the shape compiles) at its batch counts: an
        inferred shape at 1..``max_count``, a shape a traffic file names at
        1..its own (said under ``named_counts``, only where a file names one:
        the line of a cell that names none is what it always was), both capped
        by the service's max_batch."""
        def upto(n: int) -> range:
            return range(1, min(n, self.cluster.codec.max_batch) + 1)

        missed = {}
        for label, (call, rows, k) in shapes.items():
            data = np.random.default_rng(k).integers(0, 256, (rows, k), dtype=np.uint8)
            m = self._exact_batches(lambda: call(data), upto(named.get(label, max_count)))
            if m:
                missed[label] = m
        out = {"shapes": list(shapes), "counts": len(upto(max_count)), "missed": missed}
        if named:
            out["named_counts"] = {label: len(upto(n)) for label, n in named.items()}
        return out

    def warm_encode(self, object_sizes: list[int], max_count: int, named_shapes=()) -> dict:
        """Encode, through CodecService.encode_tactic, every (mode, shard
        bucket) a PUT of these object sizes produces, at batch counts
        1..min(max_count, the service's max_batch); and, through
        CodecService.encode, every shape the traffic file names
        (``warm.encode_shapes``: {n, m, shard_bytes, max_count}) that the
        harness cannot infer from a PUT, at 1..its max_count."""
        from chubaofs_tpu.blobstore.access import select_code_mode
        from chubaofs_tpu.codec.codemode import get_tactic
        from chubaofs_tpu.codec.service import bucket_len

        access, codec = self.cluster.access, self.cluster.codec
        shapes: dict[str, tuple] = {}
        for size in sorted(set(object_sizes)):
            mode = select_code_mode(size, access.policies)
            t = get_tactic(mode)
            for blob in {min(access.max_blob_size, size - off)
                         for off in range(0, size, access.max_blob_size)}:
                k = t.shard_size(blob)
                shapes[f"{mode.name}/{bucket_len(k)}"] = (lambda data, t=t: codec.encode_tactic(t, data), t.N, k)
        named = {}
        for s in named_shapes:
            n, m, k = s["n"], s["m"], s["shard_bytes"]
            label = f"{n}+{m}/{bucket_len(k)}"
            shapes.setdefault(label, (lambda data, n=n, m=m: codec.encode(n, m, data), n, k))
            named[label] = s["max_count"]
        return self._warm_shapes(shapes, max_count, named)

    def warm_decode(self, locations: list[str], max_count: int, named_shapes=()) -> dict:
        """Decode, through CodecService.decode_rows, every (rows wanted, shard
        bucket) a whole-blob GET of these objects needs with what is down, at
        batch counts 1..min(max_count, max_batch). A unit is down where its
        node is not routed OR the cluster manager holds its disk other than
        NORMAL (one disk of a routed node declared broken). And every shape
        the traffic file names (``warm.decode_shapes``: {n, m, rows,
        shard_bytes, max_count}) that no GET shows, such as a repair's decode
        by a local stripe, at 1..its max_count."""
        from chubaofs_tpu.blobstore.clustermgr import DISK_NORMAL
        from chubaofs_tpu.codec.codemode import get_tactic
        from chubaofs_tpu.codec.service import bucket_len

        codec, cm, nodes = self.cluster.codec, self.cluster.cm, self.cluster.nodes

        def up(u) -> bool:
            return u.node_id in nodes and cm.disk_status(u.disk_id) == DISK_NORMAL

        def decode(n, m, present, want):
            return lambda surv: codec.decode_rows(n, m, present, surv, want)

        shapes: dict[str, tuple] = {}
        for token in locations:
            loc = json.loads(token)
            t = get_tactic(loc["code_mode"])
            for b in loc["blobs"]:
                units = cm.get_volume(b["vid"]).units
                want = [u.index for u in units if u.index < t.N and not up(u)]
                if not want:
                    continue
                present = [u.index for u in units if u.index < t.N + t.M and up(u)][: t.N]
                k = t.shard_size(b["size"])
                shapes.setdefault(f"{t.N}+{t.M}/want{len(want)}/{bucket_len(k)}",
                                  (decode(t.N, t.M, present, want), t.N, k))
        named = {}
        for s in named_shapes:
            n, m, r, k = s["n"], s["m"], s["rows"], s["shard_bytes"]
            label = f"{n}+{m}/want{r}/{bucket_len(k)}"
            # the first r positions lost, the next n answer: some r-row pattern of the code
            shapes.setdefault(label, (decode(n, m, list(range(r, r + n)), list(range(r))), n, k))
            named[label] = s["max_count"]
        return self._warm_shapes(shapes, max_count, named)

    def gather_window(self) -> int:
        """Blob gathers one GET keeps in flight (the access layer's pipeline
        window): bounds how many decode jobs the streams can queue at once."""
        return max(1, int(self.cluster.access.pipeline_window))

    # -- faults and switches the program already has -------------------------

    def node_down(self, node_ids: list[int]) -> None:
        """Permanent loss of whole nodes, as chaos/scheduler.py `_kill` does it:
        the engine is closed and removed from the routing table. Done under the
        daemon's runner lock, which the background tick also takes: a tick that
        still held the engine while its native metadb closed took the process
        down with a SIGSEGV once in some forty runs (my chip run, PR 24)."""
        def kill(cluster) -> None:
            for nid in node_ids:
                cluster.nodes.pop(nid).close()

        self.daemon.runner.call_with("cluster", kill)

    def switch_off(self, names: list[str]) -> None:
        for name in names:
            self.cluster.scheduler.switches.set(name, False)

    # -- what the timed path left on the blobnodes -------------------------

    def stripes(self, token: str) -> list[dict]:
        """For each blob of an object: its size, the mode's name, and every
        stripe position's stored bytes (None where no shard can be read)."""
        from chubaofs_tpu.codec.codemode import CodeMode

        loc = json.loads(token)
        out = []
        for b in loc["blobs"]:
            shards: list[bytes | None] = []
            for u in self.cluster.cm.get_volume(b["vid"]).units:
                node = self.cluster.nodes.get(u.node_id)
                try:
                    shards.append(None if node is None else node.get_shard(u.vuid, b["bid"]))
                except Exception:  # a missing or damaged shard is a finding, not a crash
                    shards.append(None)
            out.append({"size": b["size"], "mode": CodeMode(loc["code_mode"]).name,
                        "shards": shards})
        return out

    def stop(self) -> None:
        self.daemon.stop()
