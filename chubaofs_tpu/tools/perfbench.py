"""Hot-path perf harness — the mdtest/fio analog over real daemon clusters.

Reference counterpart: the published evaluation suite
(/root/reference/docs/source/evaluation/ — mdtest file create/stat/removal
ops/s, fio streaming MB/s, tiny-file TPS; BASELINE.md carries the numbers
from a 10-node 32-core cluster on 10 Gb/s networking). This harness measures
the SAME axes against a ProcCluster of real subprocess daemons, so every op
crosses the client/metanode/datanode process boundaries the way the
reference's benchmarks cross machines.

Single-host caveat (PERF.md records the scaling argument next to these
numbers): everything here shares one host's cores, so absolute figures are
per-node floors, not cluster aggregates. The reference's cluster numbers
scale out with node count because metadata partitions and data partitions
shard across machines — the same sharding this repo implements — so the
honest comparison is ops/s-per-metanode and MB/s-per-datanode.

Usage:
    python -m chubaofs_tpu.tools.perfbench [--clients N] [--files N]
        [--stream-mb N] [--root DIR]

Prints exactly ONE JSON line: {"metric": "mdtest_create_ops", ...,
"configs": {...}}. Diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_metadata(cluster, volume: str, n_files: int, n_clients: int) -> dict:
    """mdtest analog: create / stat / remove ops/s, 1 and N clients.

    Each client works in its own directory (mdtest -u), so creates contend
    on the shared metanode partitions, not on a single directory lock."""
    from chubaofs_tpu.sdk.cluster import RemoteCluster

    out = {}
    for clients in sorted({1, n_clients}):
        fss = [RemoteCluster(cluster.master_addrs).client(volume)
               for _ in range(clients)]
        per = n_files // clients
        for fs, c in zip(fss, range(clients)):
            fs.mkdirs(f"/md{clients}/c{c}")

        def phase(verb):
            def client_run(args):
                fs, c = args
                base = f"/md{clients}/c{c}"
                for i in range(per):
                    verb(fs, f"{base}/f{i}")
            with ThreadPoolExecutor(clients) as pool:
                list(pool.map(client_run, zip(fss, range(clients))))

        dt = _timed(lambda: phase(lambda fs, p: fs.create(p)))
        out[f"create_ops_{clients}c"] = round(per * clients / dt, 1)
        dt = _timed(lambda: phase(lambda fs, p: fs.stat(p)))
        out[f"stat_ops_{clients}c"] = round(per * clients / dt, 1)
        dt = _timed(lambda: phase(lambda fs, p: fs.unlink(p)))
        out[f"remove_ops_{clients}c"] = round(per * clients / dt, 1)
        log(f"  mdtest {clients} client(s): "
            f"create={out[f'create_ops_{clients}c']} "
            f"stat={out[f'stat_ops_{clients}c']} "
            f"remove={out[f'remove_ops_{clients}c']} ops/s")
    return out


def bench_stream(cluster, volume: str, total_mb: int) -> dict:
    """fio analog: sequential write then read MB/s through the chain-repl
    path (one streaming client, 1 MiB IOs, 3-replica write amplification)."""
    from chubaofs_tpu.sdk.cluster import RemoteCluster

    fs = RemoteCluster(cluster.master_addrs).client(volume)
    chunk = b"\xa5" * (1 << 20)
    ino = fs.create("/stream.bin")
    t0 = time.perf_counter()
    for i in range(total_mb):
        fs.write_at(ino, i << 20, chunk)
    wdt = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = 0
    for i in range(total_mb):
        got += len(fs.read_at(ino, i << 20, 1 << 20))
    rdt = time.perf_counter() - t0
    assert got == total_mb << 20
    out = {"seq_write_mbps": round(total_mb / wdt, 1),
           "seq_read_mbps": round(total_mb / rdt, 1)}
    log(f"  stream: write={out['seq_write_mbps']} read={out['seq_read_mbps']} MB/s")
    return out


def bench_smallfile(cluster, volume: str, n_files: int, size: int = 4096) -> dict:
    """Tiny-file TPS (create+write+read of 4 KiB files — the tiny-extent
    path; ref evaluation tiny.md)."""
    from chubaofs_tpu.sdk.cluster import RemoteCluster

    fs = RemoteCluster(cluster.master_addrs).client(volume)
    fs.mkdirs("/small")
    payload = b"s" * size
    t0 = time.perf_counter()
    for i in range(n_files):
        ino = fs.create(f"/small/f{i}")
        fs.write_at(ino, 0, payload)
    wdt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(n_files):
        assert len(fs.read_file(f"/small/f{i}")) == size
    rdt = time.perf_counter() - t0
    out = {"smallfile_write_tps": round(n_files / wdt, 1),
           "smallfile_read_tps": round(n_files / rdt, 1)}
    log(f"  smallfile: write={out['smallfile_write_tps']} "
        f"read={out['smallfile_read_tps']} TPS")
    return out


def bench_meta_scale(root: str, volume: str = "metascale", dirs: int = 16,
                     seed_files: int = 24, files_per_phase: int = 12,
                     metanodes: int = 9, phases: tuple = (1, 3, 4),
                     wire_ms: float = 40.0,
                     workers_per_partition: int = 4) -> dict:
    """Metadata scale-out proof (ISSUE 15): aggregate create ops/s as ONE
    volume grows from 1 to >=4 meta partitions spread over >=2 metanode
    processes, via mid-range LOAD splits at the median live inode.

    Cluster shape: `metanodes` metanode daemons (9, so the measured phases'
    3-replica partition groups land on DISJOINT node triples — a node
    hosting two groups serializes their commit rounds through its single
    raft drain-pump thread, and with only 3 metanodes every node
    participates in every commit, so partitioning could spread nothing),
    and a deterministic `wire_ms` delay at the raft.drain
    failpoint in every daemon — the WAL-fsync + replication RTT every real
    deployment pays per commit round (bench_put_pipeline's `_wire`
    rationale: in-process commits cost ~0 wall, so without it there is
    nothing for partition parallelism to overlap on a shared CI host).

    Methodology: WEAK scaling — client concurrency grows with the partition
    count (`workers_per_partition` x partitions), the mdtest scale-out
    convention: a metadata plane that splits exists to serve MORE
    concurrent clients, and holding the client herd fixed would only
    re-measure per-client latency. The workload is the directory-heavy
    tenant of arxiv 1709.05365: `dirs` directories created INTERLEAVED
    with seed files so the dir inos spread across the inode range (a
    median split then leaves directories on BOTH sides), parents resolved
    once (the mdtest cached-handle shape). Between phases
    /metaPartition/split grows the layout — the same machinery
    CFS_META_SPLIT_OPS drives from heartbeat loads, triggered explicitly
    so phase boundaries are deterministic: splitting the TAIL chains a
    cursor split (dead lower half, headroom-capped hot half, fresh tail),
    splitting a mid partition adds one. Dirs on allocating partitions keep
    the combined single-commit path; dirs on dead ranges pay dentry-local
    + tail-inode two-op commits. Each phase warms one untimed create per
    dir first (fills the client's full-partition cache so ERANGE probe
    rounds stay out of the window).

    Correctness gates (the tier-1 smoke): every phase reaches its exact
    partition count, ranges stay contiguous/disjoint, no duplicate ino is
    ever handed out, every create lands exactly once (per-dir readdir
    census), and the final layout has raft leaders on >=2 distinct
    metanodes. The scaling numbers ride the BENCH json (PERF.md policy:
    no perf floors in tier-1 on co-tenant CI hosts)."""
    import stat as stat_mod

    from chubaofs_tpu.meta.service import RemoteMetaNode
    from chubaofs_tpu.sdk.cluster import RemoteCluster
    from chubaofs_tpu.testing.harness import ProcCluster

    cluster = ProcCluster(
        root, masters=1, metanodes=metanodes, datanodes=0,
        env={"CFS_FAILPOINTS": f"raft.drain=delay({wire_ms / 1000.0})"}
        if wire_ms > 0 else None)
    try:
        return _meta_scale_phases(cluster, volume, dirs, seed_files,
                                  files_per_phase, phases,
                                  workers_per_partition,
                                  RemoteCluster, RemoteMetaNode, stat_mod)
    finally:
        cluster.close()


def _meta_scale_phases(cluster, volume, dirs, seed_files, files_per_phase,
                       phases, workers_per_partition,
                       RemoteCluster, RemoteMetaNode, stat_mod) -> dict:
    mc = cluster.client_master()
    mc.create_volume(volume, cold=True)
    setup_fs = RemoteCluster(cluster.master_addrs).client(volume)
    expected: dict[int, set] = {d: set() for d in range(dirs)}
    dir_inos: list[int] = []
    for d in range(dirs):
        dir_inos.append(setup_fs.mkdirs(f"/d{d}"))
        for i in range(seed_files):
            setup_fs.create(f"/d{d}/seed{i}")
            expected[d].add(f"seed{i}")

    max_workers = workers_per_partition * phases[-1]
    fss = []
    for _ in range(max_workers):
        fs = RemoteCluster(cluster.master_addrs).client(volume)
        # the measurement window outlives the default view TTL; routing
        # refreshes are error-driven (EWRONGPART) during the window, so a
        # mid-window TTL refresh would only clear the full-partition cache
        # and re-pay ERANGE probe rounds
        fs.meta.VIEW_TTL = 300.0
        fss.append(fs)
    out: dict = {}

    def mps():
        return sorted(mc.meta_partitions(volume), key=lambda m: m["start"])

    def split_to(target: int):
        """Split toward `target` partitions, always splitting the partition
        holding the MOST measured directories (tie: the highest range —
        later partitions are the ones with allocation headroom, and
        splitting those keeps the combined-create path alive)."""
        while len(mps()) < target:
            def dirs_in(m):
                end = m["end"] if m["end"] > 0 else (1 << 63)
                return sum(1 for ino in dir_inos if m["start"] <= ino < end)

            cands = sorted(mps(), key=lambda m: (-dirs_in(m), -m["start"]))
            for m in cands:
                new_pid = mc.split_meta_partition(
                    volume, m["partition_id"])["new_pid"]
                if new_pid:
                    break
            else:
                raise RuntimeError("no partition would split "
                                   f"(view: {mps()})")

    def create_one(fs, parent: int, name: str) -> int:
        """One create with the parent handle CACHED (no per-create path
        resolution): the combined single-commit fast path when the parent's
        partition allocates, else the two-op flow — FsClient._create_node's
        exact contract, minus the resolve."""
        mode = stat_mod.S_IFREG | 0o644
        inode = fs.meta.create_file(parent, name, mode, quota_ids=[])
        if inode is None:
            inode = fs.meta.create_inode(mode)
            fs.meta.create_dentry(parent, name, inode.ino, inode.mode)
        return inode.ino

    def measure(tag: str, parts: int) -> float:
        workers = workers_per_partition * parts
        # warm-up: one untimed create per dir per client herd — routes
        # refresh, ERANGE probes land in _full_pids, raft leaders settle
        for d in range(dirs):
            create_one(fss[d % workers], dir_inos[d], f"{tag}_warm")
            expected[d].add(f"{tag}_warm")
        inos: list[list[int]] = [[] for _ in range(workers)]

        def worker(w: int):
            fs = fss[w]
            for d in range(w, dirs, workers):
                parent = dir_inos[d]
                for i in range(files_per_phase):
                    inos[w].append(create_one(fs, parent, f"{tag}_{i}"))
                    expected[d].add(f"{tag}_{i}")

        t0 = time.perf_counter()
        with ThreadPoolExecutor(workers) as pool:
            list(pool.map(worker, range(workers)))
        dt = time.perf_counter() - t0
        made = dirs * files_per_phase
        flat = [i for per in inos for i in per]
        assert len(flat) == made and len(set(flat)) == len(flat), \
            "duplicate or missing ino"
        rate = made / dt
        log(f"  meta-scale {parts}p x{workers}w: {made} creates in "
            f"{dt:.2f}s = {rate:.1f} ops/s")
        return rate

    for parts in phases:
        split_to(parts)
        view = mps()
        assert len(view) == parts, (parts, view)
        # contiguous + disjoint ranges: no ino owned by zero/two partitions
        for a, b in zip(view, view[1:]):
            assert a["end"] == b["start"], f"range gap/overlap: {view}"
        out[f"meta_create_ops_{parts}p"] = round(measure(f"p{parts}", parts), 1)

    # census: every create landed exactly once, across every boundary
    census_fs = RemoteCluster(cluster.master_addrs).client(volume)
    for d in range(dirs):
        names = census_fs.readdir(f"/d{d}")
        assert len(names) == len(set(names)), f"dup dentries in /d{d}"
        missing = expected[d] - set(names)
        extra = set(names) - expected[d]
        assert not missing and not extra, \
            f"/d{d}: missing={sorted(missing)[:4]} extra={sorted(extra)[:4]}"

    # leader spread: the final layout's raft leaders live on >=2 metanodes
    leaders: dict[int, int] = {}
    for n in mc.get_cluster()["nodes"]:
        if n["kind"] != "meta" or not n["addr"]:
            continue
        h = RemoteMetaNode(n["addr"])
        try:
            for pid, is_lead in h.partition_leaders().items():
                if is_lead:
                    leaders[pid] = n["node_id"]
        finally:
            h.close()
    view_pids = {m["partition_id"] for m in mps()}
    lead_nodes = {leaders[pid] for pid in view_pids if pid in leaders}
    out["meta_leader_nodes"] = len(lead_nodes)
    assert len(lead_nodes) >= 2, \
        f"partitions not spread: leaders {leaders} for {sorted(view_pids)}"
    lo, hi = phases[0], phases[-1]
    out["meta_scale_speedup"] = round(
        out[f"meta_create_ops_{hi}p"]
        / max(0.001, out[f"meta_create_ops_{lo}p"]), 2)
    log(f"  meta-scale: {lo}p -> {hi}p aggregate create speedup "
        f"x{out['meta_scale_speedup']}, leaders on "
        f"{out['meta_leader_nodes']} metanodes")
    return out


def bench_raft_commit(wal_root: str, n_ops: int = 600) -> dict:
    """Raft-commit microbench: single-group commits/s at 1/8/64 concurrent
    proposers — the exact axis the round-5 metadata gap was diagnosed on
    (VERDICT: the reference drains up to 64 pending proposals into one
    replication round, raft.go:283-311; this measures our group commit the
    same way). A real 3-node MultiRaft over InProcNet with per-group WALs;
    every proposer loops propose -> wait-for-apply, so any coalescing comes
    ONLY from the consensus layer's pending-queue drain, not the harness."""
    from chubaofs_tpu.raft import InProcNet, MultiRaft, NotLeaderError, StateMachine
    from chubaofs_tpu.raft.server import TickLoop, run_until

    class _CountSM(StateMachine):
        def __init__(self):
            self.applied = 0

        def apply(self, data, index):
            self.applied += 1
            return index

        def snapshot(self):
            return b""

        def restore(self, data):
            pass

    net = InProcNet()
    nodes = {i: MultiRaft(i, net, wal_dir=os.path.join(wal_root, f"n{i}"))
             for i in (1, 2, 3)}
    for n in nodes.values():
        n.create_group(1, [1, 2, 3], _CountSM())
    assert run_until(net, lambda: any(n.is_leader(1) for n in nodes.values()))
    lead = next(n for n in nodes.values() if n.is_leader(1))
    loop = TickLoop(list(nodes.values()))
    loop.start()
    out = {}
    try:
        for clients in (0, 1, 8, 64):
            # clients=0 is the UNBATCHED control: max_batch=1 defeats group
            # commit (one log-append + WAL flush + fan-out per proposal, the
            # pre-batching behavior) under a single proposer — the baseline
            # the 64-proposer batched rate is judged against
            unbatched = clients == 0
            if unbatched:
                clients, lead.groups[1].core.max_batch = 1, 1
            else:
                lead.groups[1].core.max_batch = 64
            per = max(1, n_ops // clients)

            def proposer(c):
                for i in range(per):
                    for _ in range(3):  # stable net: retries are paranoia
                        try:
                            lead.propose(1, ("op", c, i)).result(timeout=30)
                            break
                        except NotLeaderError:
                            time.sleep(0.05)

            def one_pass() -> float:
                t0 = time.perf_counter()
                with ThreadPoolExecutor(clients) as pool:
                    list(pool.map(proposer, range(clients)))
                return per * clients / (time.perf_counter() - t0)

            lead.drain_stats_reset()
            # best-of-2: this is a 2-vCPU shared dev host; a co-tenant burst
            # in either pass must not masquerade as a batching regression
            rate = max(one_pass(), one_pass())
            key = "raft_commit_ops_1p_unbatched" if unbatched \
                else f"raft_commit_ops_{clients}p"
            out[key] = round(rate, 1)
            st = lead.drain_stats_snapshot()  # consistent multi-field read
            avg_b = st["entries"] / max(1, st["rounds"])
            if not unbatched:
                out[f"raft_commit_batch_{clients}p"] = round(avg_b, 1)
            log(f"  raft-commit {clients} proposer(s)"
                f"{' UNBATCHED' if unbatched else ''}: {out[key]} commits/s "
                f"(avg drained batch {avg_b:.1f}, max {st['max_batch']})")

        # the batch-aware submit path itself: 64 proposals in flight as
        # 8 clients x 8-deep propose_batch windows — what a batching caller
        # (combined-op SDK flows, freelist sweeps) actually exercises
        from concurrent.futures import wait as fut_wait

        per = max(1, n_ops // 64)

        def batch_proposer(c):
            for i in range(per):
                for _ in range(3):
                    try:
                        futs = lead.propose_batch(
                            1, [("op", c, i, j) for j in range(8)])
                        fut_wait(futs, timeout=30)
                        break
                    except NotLeaderError:
                        time.sleep(0.05)

        def batch_pass() -> float:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(8) as pool:
                list(pool.map(batch_proposer, range(8)))
            return per * 8 * 8 / (time.perf_counter() - t0)

        out["raft_commit_ops_8x8"] = round(max(batch_pass(), batch_pass()), 1)
        log(f"  raft-commit 8 clients x 8-deep propose_batch: "
            f"{out['raft_commit_ops_8x8']} commits/s")
    finally:
        loop.stop()
    return out


def bench_put_pipeline(root: str, blob_kb: int = 64, n_puts: int = 8,
                       blob_counts: tuple = (1, 4, 16),
                       n_nodes: int = 6, wire_ms: float = 10.0) -> dict:
    """Blobstore data-path pipeline A/B (ISSUE 4): PUT (and a widest-object
    GET) throughput through a real AccessGateway HTTP hop, on two axes in
    ONE run — pipeline on/off (windowed encode->write overlap vs the
    serialized per-blob path) x pooled/unpooled RPC (keep-alive connection
    pool vs connect-per-request). Multi-blob objects are forced by shrinking
    max_blob_size, so the 16-blob config exercises the full window without
    64 MiB objects.

    Two latency regimes are emitted side by side: the raw in-process numbers
    (blobnodes are objects in this process — the shard hop costs ~0 wire
    time, so overlap can only exploit CPU/file-IO parallelism), and `_wire`
    configs with a deterministic `wire_ms` per-shard delay injected at the
    access.write_shard / access.read_shard chaos failpoints — the
    deployment shape, where the gateway->blobnode hop is a network RTT and
    hiding it behind the codec is the whole point of the pipeline (the
    reference's own numbers ride a 10 Gb/s fabric, BASELINE.md). Also emits
    the pipelined/serial speedups, the realized overlap ratio from the
    access registry, and the rpc pool hit rate over the pooled phase."""
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.blobstore.gateway import AccessClient, AccessGateway
    from chubaofs_tpu.rpc.pool import NullPool
    from chubaofs_tpu.utils import exporter

    c = MiniCluster(os.path.join(root, "blob"), n_nodes=n_nodes,
                    disks_per_node=2)
    c.access.max_blob_size = blob_kb * 1024
    gw = AccessGateway(c.access)
    rpc_reg = exporter.registry("rpc")

    def pool_ctrs() -> tuple[float, float]:
        return (rpc_reg.counter("pool_reuse").value,
                rpc_reg.counter("pool_miss").value)

    out: dict = {}
    rng_data = {nb: os.urandom(nb * blob_kb * 1024) for nb in blob_counts}
    clients = {False: AccessClient([gw.addr], pool=NullPool()),
               True: AccessClient([gw.addr])}
    variants = [(pooled, window)
                for pooled in (False, True) for window in (0, 3)]
    pool_hits = pool_misses = 0.0
    try:
        # warm every path once (vuid creation, codec jit shapes, pool fill)
        for pooled, window in variants:
            c.access.pipeline_window = window
            loc = clients[pooled].put(rng_data[max(blob_counts)])
            assert clients[pooled].get(loc) == rng_data[max(blob_counts)]
        def one_variant(pooled: bool, window: int, suffix: str):
            nonlocal pool_hits, pool_misses
            client = clients[pooled]
            c.access.pipeline_window = window
            variant = (f"{'pipe' if window else 'serial'}_"
                       f"{'pooled' if pooled else 'nopool'}")
            if pooled:
                reuse0, miss0 = pool_ctrs()
            for nb in blob_counts:
                data = rng_data[nb]
                # per-op timing, min-of-puts (timeit discipline): a co-
                # tenant burst inflates SOME puts on this shared host;
                # the fastest op is what the path can actually do
                best = 1e9
                for _ in range(n_puts):
                    t0 = time.perf_counter()
                    loc = client.put(data)
                    best = min(best, time.perf_counter() - t0)
                key = f"put_{nb}b_{variant}{suffix}_mbps"
                rate = round(len(data) / best / 2**20, 1)
                out[key] = max(out.get(key, 0.0), rate)
                assert client.get(loc) == data
            # GET readahead A/B on the widest object only
            nb = max(blob_counts)
            loc = client.put(rng_data[nb])
            best = 1e9
            for _ in range(n_puts):
                t0 = time.perf_counter()
                client.get(loc)
                best = min(best, time.perf_counter() - t0)
            gkey = f"get_{nb}b_{variant}{suffix}_mbps"
            out[gkey] = max(out.get(gkey, 0.0), round(
                len(rng_data[nb]) / best / 2**20, 1))
            if pooled:
                reuse1, miss1 = pool_ctrs()
                pool_hits += reuse1 - reuse0
                pool_misses += miss1 - miss0

        # 2 interleaved passes per regime, best-of per config: a co-tenant
        # burst on this shared host must not masquerade as (or mask) a
        # pipeline effect
        from chubaofs_tpu import chaos

        suffixes = ("",) if wire_ms <= 0 else ("", "_wire")
        for suffix in suffixes:
            if suffix:
                # deterministic emulated gateway->blobnode RTT on every
                # shard read/write — the deployment's latency shape
                chaos.arm("access.write_shard", f"delay({wire_ms / 1000.0})")
                chaos.arm("access.read_shard", f"delay({wire_ms / 1000.0})")
            try:
                for _ in range(2):
                    for pooled, window in variants:
                        one_variant(pooled, window, suffix)
            finally:
                if suffix:
                    chaos.disarm("access.write_shard")
                    chaos.disarm("access.read_shard")
        for k in sorted(out):
            log(f"  put-pipeline {k} = {out[k]}")
        out["rpc_pool_hit_rate"] = round(
            pool_hits / max(1.0, pool_hits + pool_misses), 3)
        nb = max(blob_counts)
        for suffix in suffixes:
            out[f"put_pipeline_speedup{suffix}"] = round(
                out[f"put_{nb}b_pipe_pooled{suffix}_mbps"]
                / max(0.001, out[f"put_{nb}b_serial_nopool{suffix}_mbps"]), 2)
        ov = exporter.registry("access").summary("put_overlap_ratio").snapshot()
        out["put_overlap_ratio_avg"] = round(
            ov["sum"] / ov["count"], 2) if ov["count"] else 0.0
        log(f"  put-pipeline speedup({nb}b) x{out['put_pipeline_speedup']} raw"
            + (f" / x{out['put_pipeline_speedup_wire']} wire" if wire_ms > 0
               else "")
            + f", overlap {out['put_overlap_ratio_avg']}, "
              f"pool hit rate {out['rpc_pool_hit_rate']}")
    finally:
        gw.stop()
        c.close()
    return out


def bench_repair(root: str, n_nodes: int = 6, disks_per_node: int = 2,
                 stripes: int = 16, blob_kb: int = 256,
                 wire_ms: float = 2.0, window: int = 4) -> dict:
    """Repair-plane A/B (ISSUE 7): stripes/s rebuilt off a broken disk,
    serial control (repair_window=0) vs the windowed download↔decode
    pipeline, under a deterministic `wire_ms` per-shard-read delay — the
    deployment's gateway->blobnode RTT, same rationale as
    bench_put_pipeline's _wire regime (in-process reads cost ~0, so without
    it there is nothing for the pipeline to hide). The broken source is a
    KILLED NODE (engine closed and unrouted), not a merely-flagged disk, so
    every rebuilt row really is reconstructed from survivors through the
    batched device decode — a flagged-but-alive disk would let the migrate
    degenerate to a copy and the decode leg would measure nothing. Each
    phase runs on a fresh cluster with identical payloads; every repaired
    object must read back byte-identical (a miscompare raises). Also emits
    the realized download/decode overlap ratio (from the repair spans, via
    the scheduler's cfs_scheduler_repair_overlap_ratio summary) and
    bytes-downloaded-per-repaired-shard."""
    from chubaofs_tpu import chaos
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.blobstore.clustermgr import DISK_BROKEN
    from chubaofs_tpu.utils import exporter

    reg = exporter.registry("scheduler")
    payloads = [os.urandom(blob_kb * 1024) for _ in range(stripes)]

    def phase(label: str, win: int) -> tuple[int, float]:
        c = MiniCluster(os.path.join(root, label), n_nodes=n_nodes,
                        disks_per_node=disks_per_node)
        try:
            c.worker.set_repair_window(win)  # resizes the stripe pool too
            locs = [c.access.put(p) for p in payloads]
            # kill the most-loaded node: its disks' repair tasks then cover
            # the widest reconstruct set this little cluster can produce
            load = {n: 0 for n in c.nodes}
            for d in c.cm.disks.values():
                load[d.node_id] = load.get(d.node_id, 0) + d.chunk_count
            victim = max(load, key=load.get)
            c.nodes.pop(victim).close()
            for d in c.cm.disks.values():
                if d.node_id == victim:
                    c.cm.set_disk_status(d.disk_id, DISK_BROKEN)
            shards0 = reg.counter("repaired_shards").value
            if wire_ms > 0:
                chaos.arm("blobnode.get_shard", f"delay({wire_ms / 1000.0})")
            t0 = time.perf_counter()
            try:
                c.scheduler.check_disks()
                while c.worker.run_once():
                    pass
                dt = time.perf_counter() - t0
            finally:
                if wire_ms > 0:
                    chaos.disarm("blobnode.get_shard")
            rebuilt = int(reg.counter("repaired_shards").value - shards0)
            for loc, p in zip(locs, payloads):
                assert c.access.get(loc) == p, \
                    f"repaired stripe miscompares ({label})"
            return rebuilt, dt
        finally:
            c.close()

    out: dict = {}
    bytes0 = reg.counter("repair_bytes_downloaded").value
    rebuilt_s, dt_s = phase("serial", 0)
    # pass the writer's bucket spec: a bucket-less reader minting the family
    # first would make the scheduler's later observe() fail loudly
    ov0 = reg.summary("repair_overlap_ratio",
                      buckets=exporter.RATIO_BUCKETS).snapshot()
    rebuilt_p, dt_p = phase("pipelined", window)
    ov1 = reg.summary("repair_overlap_ratio",
                      buckets=exporter.RATIO_BUCKETS).snapshot()
    dl_bytes = reg.counter("repair_bytes_downloaded").value - bytes0
    out["repair_rows_serial"] = rebuilt_s
    out["repair_rows_pipelined"] = rebuilt_p
    out["repair_stripes_s_serial"] = round(rebuilt_s / max(1e-9, dt_s), 1)
    out["repair_stripes_s_pipelined"] = round(rebuilt_p / max(1e-9, dt_p), 1)
    out["repair_speedup"] = round(
        out["repair_stripes_s_pipelined"]
        / max(0.001, out["repair_stripes_s_serial"]), 2)
    n_obs = ov1["count"] - ov0["count"]
    out["repair_overlap_ratio"] = round(
        (ov1["sum"] - ov0["sum"]) / n_obs, 3) if n_obs else 0.0
    total_rows = max(1, rebuilt_s + rebuilt_p)
    out["repair_bytes_per_shard"] = round(dl_bytes / total_rows, 1)
    log(f"  repair: serial {out['repair_stripes_s_serial']}/s vs pipelined "
        f"{out['repair_stripes_s_pipelined']}/s "
        f"(x{out['repair_speedup']}), overlap "
        f"{out['repair_overlap_ratio']}, "
        f"{out['repair_bytes_per_shard']} bytes/shard")
    return out


def bench_repair_codes(root: str, n_nodes: int = 17, stripes: int = 12,
                       blob_kb: int = 120, wire_ms: float = 2.0,
                       window: int = 4) -> dict:
    """Repair-traffic A/B (ISSUE 19): identical blob bytes rebuilt off a
    killed node under the product-matrix regenerating code RG6P6 (β-fetch:
    d=10 helpers each ship a GF-combined shard/5 slice, 2 shard-equivalents
    per row) vs classic RS EC12P4 (k=12 full shards per row). One disk per
    node so the kill loses exactly ONE unit per stripe — the single-loss
    regime the β path exists for; a two-disk node would alias two stripe
    positions onto the victim and silently turn the RG phase into its own
    multi-loss fallback. Same wire regime and byte-identical read-back
    rules as bench_repair. Hedged bytes are excluded from the numerator by
    the scheduler's need-aware accounting, so bytes-per-repaired-shard is
    pure required traffic. Emits per-mode bytes/shard, download
    amplification (bytes downloaded / bytes rebuilt — shard sizes differ
    across modes, amplification doesn't), stripes/s, overlap ratio, and
    the headline reduction the acceptance gate rides (>=25%; the geometry
    predicts ~67% on bytes/shard, ~83% on amplification)."""
    from chubaofs_tpu import chaos
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.blobstore.clustermgr import DISK_BROKEN
    from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
    from chubaofs_tpu.utils import exporter

    reg = exporter.registry("scheduler")

    def phase(label: str, mode: CodeMode, payloads: list[bytes]) -> dict:
        c = MiniCluster(os.path.join(root, label), n_nodes=n_nodes,
                        disks_per_node=1)
        try:
            c.worker.set_repair_window(window)
            locs = [c.access.put(p, code_mode=mode) for p in payloads]
            load = {n: 0 for n in c.nodes}
            for d in c.cm.disks.values():
                load[d.node_id] = load.get(d.node_id, 0) + d.chunk_count
            victim = max(load, key=load.get)
            c.nodes.pop(victim).close()
            for d in c.cm.disks.values():
                if d.node_id == victim:
                    c.cm.set_disk_status(d.disk_id, DISK_BROKEN)
            shards0 = reg.counter("repaired_shards").value
            bytes0 = reg.counter("repair_bytes_downloaded").value
            beta0 = reg.counter("repair_beta_shards").value
            ov0 = reg.summary("rebuild_window_occupancy",
                              buckets=exporter.BATCH_BUCKETS).snapshot()
            if wire_ms > 0:
                chaos.arm("blobnode.get_shard", f"delay({wire_ms / 1000.0})")
            t0 = time.perf_counter()
            try:
                c.scheduler.check_disks()
                while c.worker.run_once():
                    pass
                dt = time.perf_counter() - t0
            finally:
                if wire_ms > 0:
                    chaos.disarm("blobnode.get_shard")
            rebuilt = int(reg.counter("repaired_shards").value - shards0)
            dl = int(reg.counter("repair_bytes_downloaded").value - bytes0)
            ov1 = reg.summary("rebuild_window_occupancy",
                              buckets=exporter.BATCH_BUCKETS).snapshot()
            for loc, p in zip(locs, payloads):
                assert c.access.get(loc) == p, \
                    f"repaired stripe miscompares ({label})"
            shard_len = get_tactic(mode).shard_size(blob_kb * 1024)
            n_obs = ov1["count"] - ov0["count"]
            return {
                "rows": rebuilt,
                "stripes_s": round(rebuilt / max(1e-9, dt), 1),
                "bytes_per_shard": round(dl / max(1, rebuilt), 1),
                "amp": round(dl / max(1, rebuilt * shard_len), 2),
                # by the pipeline's own ORDER, not by the wall clock: each
                # time the worker takes a stripe to decode it notes how many
                # stripes' gathers it has launched and not yet consumed (that
                # stripe included); what lies beyond the one is downloads
                # launched BEFORE this decode was submitted. As a share of the
                # window's room for them: 0 = serial, 1 = always full. (The
                # wall-clock figure, repair_overlap_ratio, read 0 whenever a
                # sub-millisecond decode fell between two downloads)
                "overlap": round(((ov1["sum"] - ov0["sum"]) / n_obs - 1)
                                 / (window - 1), 3)
                if n_obs and window > 1 else 0.0,
                "beta_rows": int(reg.counter("repair_beta_shards").value
                                 - beta0),
            }
        finally:
            c.close()

    payloads = [os.urandom(blob_kb * 1024) for _ in range(stripes)]
    # discarded warmup repair: in a full run the RS decode paths arrive
    # pre-warmed by bench_repair while the PM kernel/bit-matrix lowering
    # would JIT inside the RG timed region, skewing stripes/s ~3x cold
    phase("warmup", CodeMode.RG6P6, payloads[:2])
    rg = phase("rg6p6", CodeMode.RG6P6, payloads)
    rs = phase("ec12p4", CodeMode.EC12P4, payloads)
    out = {
        "repair_codes_rows_rg": rg["rows"],
        "repair_codes_rows_rs": rs["rows"],
        "repair_codes_beta_rows": rg["beta_rows"],
        "repair_codes_bytes_per_shard_rg": rg["bytes_per_shard"],
        "repair_codes_bytes_per_shard_rs": rs["bytes_per_shard"],
        "repair_codes_amp_rg": rg["amp"],
        "repair_codes_amp_rs": rs["amp"],
        "repair_codes_reduction": round(
            1.0 - rg["bytes_per_shard"] / max(1.0, rs["bytes_per_shard"]), 3),
        "repair_codes_amp_reduction": round(
            1.0 - rg["amp"] / max(0.001, rs["amp"]), 3),
        "repair_codes_stripes_s_rg": rg["stripes_s"],
        "repair_codes_stripes_s_rs": rs["stripes_s"],
        "repair_codes_overlap_rg": rg["overlap"],
        "repair_codes_overlap_rs": rs["overlap"],
    }
    log(f"  repair-codes: RG6P6 {rg['bytes_per_shard']} B/shard "
        f"(amp x{rg['amp']}) vs EC12P4 {rs['bytes_per_shard']} B/shard "
        f"(amp x{rs['amp']}) -> -{out['repair_codes_reduction'] * 100:.0f}% "
        f"bytes, {rg['stripes_s']}/s vs {rs['stripes_s']}/s, "
        f"overlap {rg['overlap']}/{rs['overlap']}")
    return out


def _gw_driver(addr: str, url: str, n_socks: int, ops: int,
               tolerate: int = 0) -> None:
    """Subprocess body for the gateway benches' load generator: keep-alive
    S3 GETs of one presigned URL over `n_socks` http.client connections,
    one in-flight request per connection — OUT of the server's process (an
    in-process driver shares the server's GIL and measures the load
    generator, not the server). Pure stdlib: the URL is presigned by the
    parent, so the driver needs no signing code. `tolerate=1` accepts
    throttle statuses (429/503) and reports per-status counts (the QoS
    fairness bench's noisy tenant); otherwise any non-200 aborts the run.
    Protocol: connect + warm every socket, print READY, block for GO, run,
    print one JSON line {"lats": [...ms...], "statuses": {code: n}}."""
    import http.client as _hc
    import threading

    host, port = addr.rsplit(":", 1)

    def connect():
        c = _hc.HTTPConnection(host, int(port), timeout=60)  # obslint: bench driver — one keep-alive conn PER simulated client IS the workload; pooling would defeat the A/B
        c.connect()
        return c

    conns = [connect() for _ in range(n_socks)]
    for c in conns:  # warm: conn registration, framer state, a real GET
        c.request("GET", url, headers={"Host": addr})
        r = c.getresponse()
        r.read()
    print("READY", flush=True)
    sys.stdin.readline()  # GO
    n_threads = max(1, min(8, n_socks))
    chunks = [conns[t::n_threads] for t in range(n_threads)]
    lats: list[list[float]] = [[] for _ in range(n_threads)]
    statuses: list[dict] = [{} for _ in range(n_threads)]

    def run(t: int) -> None:
        mine, out, st = chunks[t], lats[t], statuses[t]
        for _ in range(ops):
            for i, c in enumerate(mine):
                t0 = time.perf_counter()
                try:
                    c.request("GET", url, headers={"Host": addr})
                    r = c.getresponse()
                    r.read()
                    status = r.status
                except Exception:
                    status = -1
                    mine[i] = connect()  # server closed a throttled conn
                out.append(time.perf_counter() - t0)
                st[status] = st.get(status, 0) + 1
                if status != 200 and not tolerate:
                    raise RuntimeError(f"gateway driver got HTTP {status}")

    threads = [threading.Thread(target=run, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for c in conns:
        c.close()
    agg: dict = {}
    for st in statuses:
        for k, v in st.items():
            agg[str(k)] = agg.get(str(k), 0) + v
    print(json.dumps({"lats": [round(x * 1e3, 3) for ch in lats for x in ch],
                      "statuses": agg}), flush=True)


_GW_DRIVER_CMD = (
    "import sys\n"
    "from chubaofs_tpu.tools.perfbench import _gw_driver\n"
    "_gw_driver(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),"
    " int(sys.argv[5]))\n")


def _paced_driver(addr: str, url: str, rate: float, duration: float,
                  warm_s: float = 1.0) -> None:
    """Subprocess body for the fairness bench's VICTIM: one keep-alive
    connection, open-loop paced at `rate` req/s for `duration` seconds —
    the tenant whose p99 the noisy neighbor must not wreck. The first
    `warm_s` seconds still COUNT toward goodput (statuses) but are
    excluded from the latency sample: phase start is when both drivers'
    connection storms land and the server's lazy worker pool spawns, a
    one-time transient that would otherwise own a small sample's p99.
    Prints the same JSON line shape as _gw_driver."""
    import http.client as _hc

    host, port = addr.rsplit(":", 1)
    c = _hc.HTTPConnection(host, int(port), timeout=60)  # obslint: bench driver — one keep-alive conn PER simulated client IS the workload; pooling would defeat the A/B
    c.request("GET", url, headers={"Host": addr})
    c.getresponse().read()
    print("READY", flush=True)
    sys.stdin.readline()
    lats: list[float] = []
    statuses: dict = {}
    t0 = time.perf_counter()
    n = 0
    while True:
        sched = t0 + n / rate
        now = time.perf_counter()
        if sched - now > 0:
            time.sleep(sched - now)
        if time.perf_counter() - t0 >= duration:
            break
        t1 = time.perf_counter()
        try:
            c.request("GET", url, headers={"Host": addr})
            r = c.getresponse()
            r.read()
            status = r.status
        except Exception:
            status = -1
            c = _hc.HTTPConnection(host, int(port), timeout=60)  # obslint: bench driver — one keep-alive conn PER simulated client IS the workload; pooling would defeat the A/B
        if t1 - t0 >= warm_s:
            lats.append(time.perf_counter() - t1)
        statuses[str(status)] = statuses.get(str(status), 0) + 1
        n += 1
    print(json.dumps({"lats": [round(x * 1e3, 3) for x in lats],
                      "statuses": statuses}), flush=True)


_PACED_DRIVER_CMD = (
    "import sys\n"
    "from chubaofs_tpu.tools.perfbench import _paced_driver\n"
    "_paced_driver(sys.argv[1], sys.argv[2], float(sys.argv[3]),"
    " float(sys.argv[4]))\n")


def _spawn_driver(cmd: str, argv: list) -> subprocess.Popen:
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen([sys.executable, "-c", cmd] + [str(a) for a in argv],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            env=env, text=True)


def _drive(procs: list, label: str) -> list[dict]:
    """READY/GO handshake + result collection for a set of driver procs."""
    for p in procs:
        if p.stdout.readline().strip() != "READY":
            raise RuntimeError(f"{label} driver died during warm-up")
    for p in procs:
        p.stdin.write("GO\n")
        p.stdin.flush()
    outs = []
    for p in procs:
        line = p.stdout.readline()
        if not line.strip():
            raise RuntimeError(f"{label} driver died mid-run")
        outs.append(json.loads(line))
    for p in procs:
        p.wait(timeout=30)
    return outs


def _p99(lats: list[float]) -> float:
    lats = sorted(lats)
    return lats[min(len(lats) - 1, int(0.99 * len(lats)))] if lats else 0.0


class _S3Fixture:
    """One FsCluster + ObjectNode the gateway benches serve: a bucket, a
    small object, presign() for driver URLs, serve()/stop() to bring an
    RPCServer up."""

    AK, SK = "benchak", "benchsk"

    def __init__(self, root: str, payload: int = 2048, qos=None):
        from chubaofs_tpu.deploy import FsCluster
        from chubaofs_tpu.objectnode.server import ObjectNode

        self.cluster = FsCluster(root, n_nodes=3, blob_nodes=6, data_nodes=0)
        self.node = ObjectNode(
            self.cluster, users={self.AK: {"secret_key": self.SK,
                                           "uid": "bench"}}, qos=qos)
        self.users = {self.AK: self.SK}
        self.srv = None
        self._payload = payload

    def serve(self):
        from chubaofs_tpu.rpc.server import RPCServer

        self.srv = RPCServer(self.node.router, metrics=False,
                             module="objectnode").start()
        return self.srv.addr

    def put_object(self, bucket: str = "bench", key: str = "obj",
                   ak: str | None = None, sk: str | None = None) -> None:
        import http.client as _hc

        from chubaofs_tpu.objectnode import auth as s3auth

        ak, sk = ak or self.AK, sk or self.SK
        host, port = self.srv.addr.rsplit(":", 1)
        for method, path, body in ((("PUT", f"/{bucket}", b"")),
                                   ("PUT", f"/{bucket}/{key}",
                                    b"\xa5" * self._payload)):
            hdrs = s3auth.sign_v4(method, path, "", {"host": self.srv.addr},
                                  ak, sk, payload=body)
            c = _hc.HTTPConnection(host, int(port))  # obslint: bench driver — one keep-alive conn PER simulated client IS the workload; pooling would defeat the A/B
            c.request(method, path, body=body, headers=hdrs)
            r = c.getresponse()
            r.read()
            c.close()
            if r.status != 200:
                raise RuntimeError(f"fixture {method} {path} -> {r.status}")

    def presign(self, bucket: str = "bench", key: str = "obj",
                ak: str | None = None, sk: str | None = None) -> str:
        from chubaofs_tpu.objectnode import auth as s3auth

        path = f"/{bucket}/{key}"
        q = s3auth.presign_v4("GET", path, self.srv.addr, ak or self.AK,
                              sk or self.SK)
        return f"{path}?{q}"

    def stop_server(self):
        if self.srv is not None:
            self.srv.stop()
            self.srv = None

    def close(self):
        self.stop_server()
        self.cluster.close()


def bench_qos_fairness(root: str, parent_rps: float = 50.0,
                       victim_rps: float = 15.0, duration: float = 4.0,
                       noisy_socks: int = 24) -> dict:
    """Multi-tenant fairness A/B (ISSUE 14): a victim tenant paced at
    victim_rps measures its GET p99 SOLO, then again while a noisy tenant
    offers ~10x the victim's load through `noisy_socks` tight-loop
    connections — with the QoS plane armed (shared parent at parent_rps,
    deficit-fair dequeue, bounded queue wait). The noisy tenant must be
    CAPPED (throttle counters nonzero, 429/503 in its status mix) while
    the victim's p99 stays within a small factor of its solo baseline and
    its goodput holds."""
    from chubaofs_tpu.utils.qos import QosPlane

    ak_n, sk_n = "noisyak", "noisysk"
    # a saturated tenant's fair-queue waiters PARK a dispatch worker for up
    # to queue_ms each; the pool must be sized above the shaped concurrency
    # or the victim waits for a WORKER, not for tokens (the reserve bucket
    # can only protect admission, not a starved pool). Set BEFORE the plane
    # is built: FairLimiter bounds its waiter herd to half this pool.
    prev_workers = os.environ.get("CFS_EVLOOP_WORKERS")
    os.environ["CFS_EVLOOP_WORKERS"] = str(max(64, noisy_socks * 2))
    qos = QosPlane(("noisyak", "benchak"), rps=parent_rps,
                   tenant_min_rps=victim_rps * 2, queue_ms=50.0,
                   queue_len=16)
    fix = _S3Fixture(os.path.join(root, "qosbench"), payload=2048, qos=qos)
    fix.node.users[ak_n] = {"secret_key": sk_n, "uid": "noisy"}
    out: dict = {}
    try:
        addr = fix.serve()
        fix.put_object()  # victim's bucket (benchak owns it)
        # noisy tenant gets its own bucket/object so ACLs stay out of the way
        fix.put_object(bucket="noisy", key="obj", ak=ak_n, sk=sk_n)
        v_url = fix.presign()
        n_url = fix.presign(bucket="noisy", key="obj", ak=ak_n, sk=sk_n)

        def victim_phase(with_noise: bool) -> tuple[float, float, dict]:
            procs = [_spawn_driver(_PACED_DRIVER_CMD,
                                   [addr, v_url, victim_rps, duration])]
            if with_noise:
                procs.append(_spawn_driver(
                    _GW_DRIVER_CMD,
                    [addr, n_url, noisy_socks,
                     max(4, int(victim_rps * 10 * duration / noisy_socks)),
                     1]))
            try:
                outs = _drive(procs, "fairness")
            finally:
                for p in procs:
                    if p.poll() is None:
                        p.kill()
            vic = outs[0]
            noisy = outs[1]["statuses"] if with_noise else {}
            ok = vic["statuses"].get("200", 0)
            goodput = ok / max(duration, 1e-9)
            return _p99(vic["lats"]), goodput, noisy

        p99_solo, goodput_solo, _ = victim_phase(False)
        p99_mixed, goodput_mixed, noisy_st = victim_phase(True)
        thr = sum(v for k, v in noisy_st.items() if k in ("429", "503", "-1"))
        served = noisy_st.get("200", 0)
        out.update({
            "qos_victim_p99_solo_ms": round(p99_solo, 2),
            "qos_victim_p99_mixed_ms": round(p99_mixed, 2),
            "qos_victim_p99_ratio": round(p99_mixed / max(p99_solo, 1e-9), 2),
            "qos_victim_goodput_solo": round(goodput_solo, 1),
            "qos_victim_goodput_mixed": round(goodput_mixed, 1),
            "qos_victim_goodput_ratio": round(
                goodput_mixed / max(goodput_solo, 1e-9), 2),
            "qos_noisy_served": served,
            "qos_noisy_throttled": thr,
        })
        log(f"  qos fairness: victim p99 {out['qos_victim_p99_solo_ms']} -> "
            f"{out['qos_victim_p99_mixed_ms']} ms "
            f"(x{out['qos_victim_p99_ratio']}), goodput ratio "
            f"{out['qos_victim_goodput_ratio']}, noisy served {served} / "
            f"throttled {thr}")
    finally:
        if prev_workers is None:
            os.environ.pop("CFS_EVLOOP_WORKERS", None)
        else:
            os.environ["CFS_EVLOOP_WORKERS"] = prev_workers
        fix.close()
        qos.close()
    return out


def bench_capacity(root: str, duration: float = 3.5, rate: float = 20.0,
                   seed: int = 7, interval: float = 0.4,
                   tenants: int = 3) -> dict:
    """Capacity-harness smoke (ISSUE 11): the cfs-capacity generator /
    collector / gate loop at seconds scale over an IN-PROCESS FsCluster
    (whose access/codec registries this process's /health evaluates),
    fronted by a real RPCServer + console so the collector exercises the
    same `/api/health` + `/api/metrics` rollup path as a daemon cluster.

    Two phases on the same seed: a CLEAN run (the gate must evaluate to a
    non-None, non-failing verdict and archive >=3 JSONL frames) and a CHAOS
    run (a sustained `blobnode.put_shard` delay under a tightened PUT p99
    objective must flip the verdict to failing, naming put_p99) — the
    regression pair that keeps the gate honest in both directions."""
    from chubaofs_tpu import chaos
    from chubaofs_tpu.console.server import Console
    from chubaofs_tpu.deploy import FsCluster
    from chubaofs_tpu.rpc.router import Router
    from chubaofs_tpu.rpc.server import RPCServer
    from chubaofs_tpu.tools.capacity import (
        Collector, LocalDriver, Workload, plan_ops)
    from chubaofs_tpu.utils import metrichist

    out: dict = {}
    c = FsCluster(os.path.join(root, "cap"), n_nodes=3, blob_nodes=6,
                  data_nodes=0)
    srv = RPCServer(Router(), module="capacity").start()
    console = Console([srv.addr])

    def phase(report: str) -> tuple[dict, dict]:
        plan = plan_ops(seed, tenants, duration, rate, 1.2,
                        keys_per_tenant=32, ramp="diurnal")
        wl = Workload(LocalDriver(c, "capvol"), plan, seed=seed, workers=4)
        col = Collector(report, console=console.addr, interval=interval)
        col.start()
        try:
            ledger = wl.run()
            # the tail burn windows land: two more polls, and three in all,
            # counted and not timed (a poll takes what the host lets it)
            want = max(3, col.frames + 2)
            deadline = time.monotonic() + 60.0
            while col.frames < want and time.monotonic() < deadline:
                time.sleep(interval)
        finally:
            col.stop()
            wl.close()
        return col.verdict(), ledger

    prev_slo = {k: os.environ.get(k)
                for k in ("CFS_SLO_PUT_P99_MS", "CFS_SLO_GET_P99_MS")}
    try:
        c.create_volume("capvol", cold=True)
        c.blobstore.access.put(b"warm" * 256)  # jit outside the window
        # clean phase: latency objectives no host reaches (an op that cold-
        # compiles on a loaded host takes seconds, and 2 s is the default),
        # so only a real fault flips it: errors, backpressure, a dark target
        for k in prev_slo:
            os.environ[k] = "600000"
        verdict, ledger = phase(os.path.join(root, "capacity-clean.jsonl"))
        out["cap_frames_clean"] = verdict["frames"]
        out["cap_verdict_clean"] = verdict["verdict"]
        out["cap_ops_ok"] = ledger["ops_ok"]
        out["cap_ops_planned"] = ledger["ops_planned"]
        out["cap_corruptions"] = len(ledger["corruptions"])
        out["cap_max_late_s"] = ledger["max_late_s"]
        log(f"  capacity clean: verdict={verdict['verdict']} "
            f"frames={verdict['frames']} ops_ok={ledger['ops_ok']}"
            f"/{ledger['ops_planned']}")
        # chaos phase: a 20 ms objective under a sustained shard-write
        # delay of ten times that, so the flip never depends on the host
        os.environ["CFS_SLO_PUT_P99_MS"] = "20"
        chaos.arm("blobnode.put_shard", "delay(0.2)")
        try:
            verdict2, _ = phase(os.path.join(root, "capacity-chaos.jsonl"))
        finally:
            chaos.disarm("blobnode.put_shard")
        out["cap_verdict_chaos"] = verdict2["verdict"]
        out["cap_chaos_flipped"] = sorted(
            {n for names in verdict2["flipped"].values() for n in names})
        log(f"  capacity chaos: verdict={verdict2['verdict']} "
            f"flipped={out['cap_chaos_flipped']}")
    finally:
        for k, v in prev_slo.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        console.stop()
        srv.stop()
        c.close()
        # the chaos phase salted the default history ring with slow-put
        # snapshots; drop it so later /health consumers start clean
        metrichist.deactivate()
    return out


def bench_rebalance_spread(root: str, duration: float = 6.0,
                           rate: float = 30.0, seed: int = 7,
                           datanodes: int = 5) -> dict:
    """Spread-reduction-under-skew A/B (ROADMAP item 9 leftover): the
    `cfs-capacity --ab-rebalance` scenario as a tracked BENCH number. The
    same seeded zipf-hot plan (s=3.0 under a spike ramp — one scorching
    volume head) runs over two daemon clusters, hot-volume rebalance sweep
    off then on; the number is the per-datanode op-spread CV the sweep
    buys back. Flight recorders stay disarmed (CFS_FLIGHT=0) so the A/B
    measures the data plane, not capture overhead."""
    import argparse

    from chubaofs_tpu.tools.capacity import run_capacity

    args = argparse.Namespace(
        seed=seed, tenants=3, zipf_s=3.0, ramp="spike", duration=duration,
        rate=rate, keys=32, workers=6, interval=0.5, masters=1,
        metanodes=3, datanodes=datanodes, failpoints="",
        daemon_env=["CFS_FLIGHT=0"], cache_mb=0, s3=False,
        rebalance_secs=1.0, autopilot=False, scenario="none")
    out: dict = {}
    res_off = run_capacity(args, rebalance=False,
                           root=os.path.join(root, "off"),
                           out_path=os.path.join(root, "cap-off.jsonl"))
    res_on = run_capacity(args, rebalance=True,
                          root=os.path.join(root, "on"),
                          out_path=os.path.join(root, "cap-on.jsonl"))
    cv_off = res_off["spread"]["cv"]
    cv_on = res_on["spread"]["cv"]
    out["cap_ab_spread_cv_off"] = cv_off
    out["cap_ab_spread_cv_on"] = cv_on
    out["cap_ab_spread_reduction"] = (round((cv_off - cv_on) / cv_off, 3)
                                      if cv_off > 0 else 0.0)
    out["cap_ab_verdict_off"] = res_off["verdict"]
    out["cap_ab_verdict_on"] = res_on["verdict"]
    log(f"  ab-rebalance: spread cv {cv_off} -> {cv_on} "
        f"(reduction {out['cap_ab_spread_reduction']})")
    return out


def bench_cache_zipf(root: str, objects: int = 32, obj_kb: int = 64,
                     gets: int = 240, zipf_s: float = 1.1,
                     wire_ms: float = 2.0, cache_mb: int = 64,
                     seed: int = 7) -> dict:
    """Cache-plane A/B (ISSUE 12): the zipfian GET workload the tiered
    read cache exists for, EC cold path vs frequency-admitted cache tier.

    Two phases over identical payloads and the SAME seeded zipfian access
    sequence (s≈1.1 — the skew regime of arxiv 1709.05365's object traces):
    a BASELINE MiniCluster with no cache (every GET pays the full shard
    gather), and a CACHE-tier cluster (one warm pass, then the measured
    pass). A deterministic `wire_ms` per-shard-read delay stands in for the
    gateway->blobnode RTT, same rationale as bench_repair: in-process reads
    cost ~0, and the cache's win IS skipping N wire round-trips per GET.
    Every GET is crc-verified against its payload — a cache serving stale
    or torn bytes fails the bench, not just the numbers. Reports per-GET
    p50/p99 for both arms, the realized hit ratio, and the p99 speedup."""
    import random
    import zlib

    from chubaofs_tpu import chaos
    from chubaofs_tpu.blobstore.cache import BlobCache
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.utils import exporter

    rng = random.Random(seed)
    payloads = [os.urandom(obj_kb * 1024) for _ in range(objects)]
    crcs = [zlib.crc32(p) for p in payloads]
    weights = [1.0 / (r + 1) ** zipf_s for r in range(objects)]
    seq = rng.choices(range(objects), weights=weights, k=gets)
    reg = exporter.registry("cache")

    def phase(label: str, cache) -> dict:
        c = MiniCluster(os.path.join(root, label), n_nodes=6, cache=cache)
        try:
            locs = [c.access.put(p) for p in payloads]
            c.access.get(locs[0])  # jit/warm the GET path outside the window
            if cache is not None:
                for i in seq:  # warm pass: the zipfian head fills the cache
                    c.access.get(locs[i])
            if wire_ms > 0:
                chaos.arm("blobnode.get_shard", f"delay({wire_ms / 1000.0})")
            lat: list[float] = []
            try:
                for i in seq:
                    t0 = time.perf_counter()
                    data = c.access.get(locs[i])
                    lat.append(time.perf_counter() - t0)
                    if zlib.crc32(data) != crcs[i]:
                        raise AssertionError(
                            f"cache bench crc miscompare on object {i}")
            finally:
                if wire_ms > 0:
                    chaos.disarm("blobnode.get_shard")
            lat.sort()
            return {"p50": lat[len(lat) // 2] * 1e3,
                    "p99": lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3}
        finally:
            c.close()

    out: dict = {}
    # the baseline arm must really be cache-less: MiniCluster(cache=None)
    # falls back to BlobCache.from_env, so a deployment-exported
    # CFS_CACHE_MB would silently arm the "EC" arm and flatten the A/B
    prev_mb = os.environ.pop("CFS_CACHE_MB", None)
    try:
        base = phase("ec", None)
    finally:
        if prev_mb is not None:
            os.environ["CFS_CACHE_MB"] = prev_mb
    lk0 = reg.counter("lookups").value
    h0 = reg.counter("hits").value
    cache = BlobCache(os.path.join(root, "cachedir"), mem_mb=cache_mb)
    cached = phase("cached", cache)
    lookups = reg.counter("lookups").value - lk0
    hits = reg.counter("hits").value - h0
    # warm pass included: the ratio spans fill + steady state, which is the
    # honest number (a steady-state-only ratio would hide admission churn)
    out["cache_zipf_hit_ratio"] = round(hits / lookups, 3) if lookups else 0.0
    out["cache_zipf_p50_ms_ec"] = round(base["p50"], 3)
    out["cache_zipf_p99_ms_ec"] = round(base["p99"], 3)
    out["cache_zipf_p50_ms_cached"] = round(cached["p50"], 3)
    out["cache_zipf_p99_ms_cached"] = round(cached["p99"], 3)
    out["cache_zipf_speedup_p99"] = round(
        base["p99"] / cached["p99"], 2) if cached["p99"] > 0 else 0.0
    log(f"  cache zipf: hit_ratio={out['cache_zipf_hit_ratio']} "
        f"p99 {out['cache_zipf_p99_ms_ec']}ms (EC) -> "
        f"{out['cache_zipf_p99_ms_cached']}ms (cached), "
        f"{out['cache_zipf_speedup_p99']}x")
    return out


def bench_ranged(root: str, blob_mb: int = 4,
                 range_kbs: tuple = (4, 64, 256, 1024),
                 gets_per: int = 4, cache_mb: int = 16,
                 seed: int = 11) -> dict:
    """Partial-stripe ranged reads (ISSUE 17): bytes-read scales with the
    RANGE, not the blob.

    One blob_mb blob (4 MiB -> a single EC12P4 stripe under the 1-AZ
    policy) served three ways per range size, with the
    cfs_access_read_bytes{kind} counter deltas turned into per-arm ratios:

      * healthy/uncached — in-window sub-shard reads only; the floor is
        shards_read/stripe_bytes < 1/4 for any <=256 KiB range
        (acceptance: the old path gathered the whole stripe every time);
      * degraded — one in-window data shard lost: range-scoped survivor
        gather + row-sliced decode, so shards_read is N x window, never
        N x shard, and decoded bytes are window-sized;
      * cached — block-granular BlobCache: the repeat pass must be all
        hits with ZERO backend shard bytes.

    Every ranged GET (healthy AND degraded) is byte-compared against the
    whole-object slice — a miscompare raises, the same correctness-first
    contract as bench_cache_zipf's crc gate. Tier-1 floors ride
    tests/test_perfbench.py at smoke size."""
    import random

    from chubaofs_tpu.blobstore.cache import BlobCache
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.codec.codemode import get_tactic
    from chubaofs_tpu.utils import exporter

    rng = random.Random(seed)
    reg = exporter.registry("access")

    def ctr(kind: str) -> float:
        return reg.counter("read_bytes", {"kind": kind}).value

    data = os.urandom(blob_mb << 20)
    out: dict = {}
    # EC12P4 needs 16 units; 9 nodes x 2 disks covers it. cache=None must
    # really mean cache-less (MiniCluster falls back to from_env otherwise)
    prev_mb = os.environ.pop("CFS_CACHE_MB", None)
    try:
        c = MiniCluster(os.path.join(root, "mc"), n_nodes=9,
                        disks_per_node=2, cache=None)
        try:
            loc = c.access.put(data)
            c.access.get(loc, 0, 4096)  # jit/warm outside the counters
            blob = loc.blobs[0]
            t = get_tactic(loc.code_mode)
            shard_len = t.shard_size(blob.size)
            stripe_bytes = t.N * shard_len  # the old whole-gather cost
            out["ranged_stripe_bytes"] = stripe_bytes
            for rkb in range_kbs:
                rlen = min(rkb * 1024, len(data))
                offs = [rng.randrange(0, len(data) - rlen + 1)
                        for _ in range(gets_per)]
                s0, q0 = ctr("shards_read"), ctr("requested")
                for off in offs:
                    if c.access.get(loc, off, rlen) != data[off:off + rlen]:
                        raise AssertionError(
                            f"healthy ranged miscompare at {off}+{rlen}")
                req = ctr("requested") - q0
                out[f"ranged_amp_{rkb}k"] = round(
                    (ctr("shards_read") - s0) / req, 3) if req else 0.0
                out[f"ranged_stripe_frac_{rkb}k"] = round(
                    (ctr("shards_read") - s0) / gets_per / stripe_bytes, 4)
            # degraded arm: lose a data shard, read windows INSIDE it so
            # every GET exercises the range-scoped decode
            vol = c.cm.get_volume(blob.vid)
            unit = vol.units[1]
            c.nodes[unit.node_id].lose_shard(unit.vuid, blob.bid)
            rlen = min(range_kbs[0] * 1024, shard_len // 2)
            offs = [shard_len + rng.randrange(0, shard_len - rlen)
                    for _ in range(gets_per)]
            s0, q0, d0 = (ctr("shards_read"), ctr("requested"),
                          ctr("decoded"))
            for off in offs:
                if c.access.get(loc, off, rlen) != data[off:off + rlen]:
                    raise AssertionError(
                        f"degraded ranged miscompare at {off}+{rlen}")
            req = ctr("requested") - q0
            out["ranged_amp_degraded"] = round(
                (ctr("shards_read") - s0) / req, 3) if req else 0.0
            out["ranged_decoded_frac_degraded"] = round(
                (ctr("decoded") - d0) / gets_per / stripe_bytes, 4)
        finally:
            c.close()
        # cached arm: block-granular fills — a repeat of the same ranges
        # is all hits, zero backend shard bytes
        cache = BlobCache(os.path.join(root, "cachedir"), mem_mb=cache_mb)
        c2 = MiniCluster(os.path.join(root, "mc2"), n_nodes=9,
                         disks_per_node=2, cache=cache)
        try:
            loc = c2.access.put(data)
            rlen = min(64 * 1024, len(data))
            offs = [rng.randrange(0, len(data) - rlen + 1)
                    for _ in range(gets_per)]
            for off in offs:  # fill pass
                if c2.access.get(loc, off, rlen) != data[off:off + rlen]:
                    raise AssertionError("cached fill-pass miscompare")
            creg = exporter.registry("cache")
            h0 = creg.counter("hits").value
            s0 = ctr("shards_read")
            for off in offs:  # repeat pass
                if c2.access.get(loc, off, rlen) != data[off:off + rlen]:
                    raise AssertionError("cached hit-pass miscompare")
            out["ranged_cached_hits"] = int(creg.counter("hits").value - h0)
            out["ranged_cached_backend_bytes"] = int(ctr("shards_read") - s0)
        finally:
            c2.close()
    finally:
        if prev_mb is not None:
            os.environ["CFS_CACHE_MB"] = prev_mb
    frac_keys = [k for k in out if k.startswith("ranged_stripe_frac_")]
    log(f"  ranged: stripe_frac per range "
        f"{ {k.split('_')[-1]: out[k] for k in frac_keys} } "
        f"degraded_amp={out['ranged_amp_degraded']} "
        f"cached_backend_bytes={out['ranged_cached_backend_bytes']}")
    return out


def bench_events(root: str, n_events: int = 10_000, puts: int = 6,
                 blob_kb: int = 64) -> dict:
    """Events-overhead smoke (ISSUE 13): the plane's two cost contracts.

    (1) Emission is cheap enough to never matter at transition rates:
    emitting `n_events` journal records (ring + rotating JSONL + counter)
    is timed wall-clock; the tier-1 floor keeps it under a generous budget.

    (2) THE HOT PATH EMITS NOTHING: a MiniCluster PUT/GET burst — the
    busiest per-op traffic in the repo — must produce ZERO events, because
    the plane records transitions, never ops. A nonzero count here is a
    correctness failure (someone wired emit() into a data path), so the
    bench raises instead of just reporting."""
    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.utils import events

    journal = events.configure(logdir=os.path.join(root, "events"))
    t0 = time.perf_counter()
    for i in range(n_events):
        events.emit("bench_tick", detail={"i": i})
    emit_s = time.perf_counter() - t0
    out = {"events_emit_10k_s": round(emit_s * (10_000 / n_events), 4),
           "events_emit_us_avg": round(emit_s / n_events * 1e6, 2)}

    c = MiniCluster(os.path.join(root, "evcluster"), n_nodes=6)
    try:
        payload = os.urandom(blob_kb * 1024)
        warm = c.access.put(payload)  # jit/vuid creation outside the count
        assert c.access.get(warm) == payload
        seq0 = journal.last_seq()
        locs = [c.access.put(payload) for _ in range(puts)]
        for loc in locs:
            assert c.access.get(loc) == payload
        hot = journal.last_seq() - seq0
        out["events_hot_path"] = hot
        if hot:
            evs, _ = journal.query(since=seq0, n=20)
            raise AssertionError(
                f"hot-path PUT/GET burst emitted {hot} events (the plane "
                f"records transitions, never per-op traffic): "
                f"{[e['type'] for e in evs]}")
    finally:
        c.close()
    log(f"  events: emit {out['events_emit_us_avg']}us/event "
        f"({out['events_emit_10k_s']}s / 10k), hot-path events "
        f"{out['events_hot_path']}")
    return out


def bench_flightrec(root: str, puts: int = 8, blob_kb: int = 64) -> dict:
    """Flight-recorder disarm floor (ISSUE 18): zero cost until armed AND
    firing.

    The recorder is threadless and hook-driven — with CFS_FLIGHT unset
    activate_from_env() touches nothing, so a PUT/GET burst must see
    (a) no flight/recorder thread anywhere in the process, (b) zero
    bundles on disk, and (c) the armed-but-quiescent arm of the A/B
    within noise of the disarmed arm: arming only registers an alert
    hook, which costs nothing until an alert transition actually fires.
    Thread or bundle leakage is a correctness failure, so the bench
    raises rather than just reporting a number."""
    import threading

    from chubaofs_tpu.blobstore.cluster import MiniCluster
    from chubaofs_tpu.utils import flightrec

    flight_dir = os.path.join(root, "flight")
    prev = {k: os.environ.pop(k, None)
            for k in ("CFS_FLIGHT", "CFS_FLIGHT_DIR")}
    out: dict = {}
    try:
        flightrec.deactivate()
        c = MiniCluster(os.path.join(root, "frcluster"), n_nodes=6)
        try:
            payload = os.urandom(blob_kb * 1024)
            warm = c.access.put(payload)  # jit/vuid creation off the clock
            assert c.access.get(warm) == payload

            def burst_med_ms() -> float:
                lat = []
                for _ in range(puts):
                    t0 = time.perf_counter()
                    loc = c.access.put(payload)
                    if c.access.get(loc) != payload:
                        raise AssertionError("flightrec burst miscompare")
                    lat.append(time.perf_counter() - t0)
                lat.sort()
                return round(lat[len(lat) // 2] * 1000, 2)

            out["flightrec_disarmed_med_ms"] = burst_med_ms()
            stray = [t.name for t in threading.enumerate()
                     if "flight" in t.name.lower()
                     or "recorder" in t.name.lower()]
            if stray:
                raise AssertionError(
                    f"disarmed flight recorder owns threads {stray} — the "
                    f"design is threadless; nothing may spin when "
                    f"CFS_FLIGHT is unset")
            if os.path.isdir(flight_dir) and os.listdir(flight_dir):
                raise AssertionError(
                    f"disarmed burst wrote bundles: {os.listdir(flight_dir)}")

            # armed-but-quiescent arm: the hook is registered, no alert
            # fires, so the hot path must be indistinguishable
            os.environ["CFS_FLIGHT"] = "1"
            os.environ["CFS_FLIGHT_DIR"] = flight_dir
            flightrec.activate_from_env()
            out["flightrec_armed_med_ms"] = burst_med_ms()
            bundles = (os.listdir(flight_dir)
                       if os.path.isdir(flight_dir) else [])
            out["flightrec_quiescent_bundles"] = len(bundles)
            if bundles:
                raise AssertionError(
                    f"armed-but-quiescent burst wrote bundles {bundles} — "
                    f"capture must only follow an alert transition or an "
                    f"explicit trigger")
        finally:
            c.close()
    finally:
        flightrec.deactivate()
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    log(f"  flightrec: burst med disarmed "
        f"{out['flightrec_disarmed_med_ms']}ms vs armed-quiescent "
        f"{out['flightrec_armed_med_ms']}ms, bundles "
        f"{out['flightrec_quiescent_bundles']}, recorder threads 0")
    return out


def run(root: str, n_files: int = 600, n_clients: int = 4,
        stream_mb: int = 64, metanodes: int = 3, datanodes: int = 3) -> dict:
    from chubaofs_tpu.testing.harness import ProcCluster

    cfg: dict = {}
    log("event plane (emission overhead + hot-path zero-events)...")
    cfg.update(bench_events(os.path.join(root, "eventsbench")))
    log("flight recorder (disarmed zero-overhead floor)...")
    cfg.update(bench_flightrec(os.path.join(root, "flightbench")))
    log("raft commit (group-commit microbench)...")
    cfg.update(bench_raft_commit(os.path.join(root, "raftbench"), n_ops=n_files))
    log("blobstore data-path pipeline (PUT overlap + pooled RPC A/B)...")
    cfg.update(bench_put_pipeline(os.path.join(root, "blobbench"),
                                  n_puts=max(3, min(8, n_files // 100))))
    log("repair plane (windowed rebuild vs serial control)...")
    cfg.update(bench_repair(os.path.join(root, "repairbench")))
    log("capacity harness (SLO gate smoke, clean + chaos)...")
    cfg.update(bench_capacity(os.path.join(root, "capbench")))

    cluster = ProcCluster(root, masters=1, metanodes=metanodes,
                          datanodes=datanodes)
    try:
        cluster.client_master().create_volume("perf", cold=False)
        log("metadata (mdtest analog)...")
        cfg.update(bench_metadata(cluster, "perf", n_files, n_clients))
        log("streaming (fio analog)...")
        cfg.update(bench_stream(cluster, "perf", stream_mb))
        log("small files (tiny.md analog)...")
        cfg.update(bench_smallfile(cluster, "perf", max(100, n_files // 4)))
    finally:
        cluster.close()
    # metadata scale-out proof (ISSUE 15): its OWN 9-metanode ProcCluster
    # (3-replica groups of the 1/3/4-partition phases on disjoint triples)
    # under the raft-persist wire regime; placed right after the main
    # cluster phases — before the core-saturating sweeps below — per the
    # PR-8/12 floor-deflation lesson, so its per-phase A/B (phase-internal
    # like the others) sees an unthrottled host
    log("metadata scale-out (1 -> 4 partitions, load splits)...")
    cfg.update(bench_meta_scale(os.path.join(root, "metascale"),
                                files_per_phase=max(12, n_files // 50)))
    # the cache A/B runs AFTER the cluster phases:
    # its two MiniClusters + tight GET loops leave a throttle-recovering
    # host deflating the md/stream floors ~2x (measured: create_ops_1c
    # 12 -> 5.5 with this phase ahead of them); both its arms are
    # phase-internal, so position costs it nothing
    log("cache plane (zipfian GET A/B, EC vs cache tier)...")
    if n_files >= 300:
        cfg.update(bench_cache_zipf(os.path.join(root, "cachebench")))
    else:  # smoke invocations get a smoke-size zipf sweep
        cfg.update(bench_cache_zipf(os.path.join(root, "cachebench"),
                                    objects=12, obj_kb=32, gets=80))
    # ranged-read A/B rides the same post-ProcCluster slot (floor-deflation
    # lesson): its MiniClusters + 4 MiB puts would throttle-deflate the
    # md/stream floors if it ran ahead of them
    log("ranged reads (byte-window gather, healthy/degraded/cached)...")
    if n_files >= 300:
        cfg.update(bench_ranged(os.path.join(root, "rangedbench")))
    else:  # smoke invocations get a smoke-size range sweep
        cfg.update(bench_ranged(os.path.join(root, "rangedbench"),
                                blob_mb=2, range_kbs=(16, 256), gets_per=2))
    # the gateway phase runs AFTER the ProcCluster phases for the same
    # reason as bench_cache_zipf (the PR-8/PR-12 floor-deflation lesson)
    log("gateway QoS fairness (noisy tenant vs victim tenant)...")
    cfg.update(bench_qos_fairness(os.path.join(root, "qosroot")))
    # repair-traffic codes A/B rides the same post-ProcCluster slot (floor-
    # deflation lesson): two more MiniClusters + a node kill each would
    # throttle-deflate the md/stream floors if they ran ahead of them
    log("repair-traffic codes (RG6P6 beta-fetch vs EC12P4 A/B)...")
    if n_files >= 300:
        cfg.update(bench_repair_codes(os.path.join(root, "repaircodes")))
    else:  # smoke invocations get a smoke-size A/B
        cfg.update(bench_repair_codes(os.path.join(root, "repaircodes"),
                                      stripes=4, blob_kb=60))
    # the rebalance-spread A/B boots two more ProcClusters — same post-
    # cluster slot (floor-deflation lesson); smoke invocations get a
    # shorter skew window over the 3-node floor
    log("rebalance spread (cfs-capacity --ab-rebalance A/B)...")
    if n_files >= 300:
        cfg.update(bench_rebalance_spread(os.path.join(root, "rebalab")))
    else:
        cfg.update(bench_rebalance_spread(os.path.join(root, "rebalab"),
                                          duration=3.0, rate=15.0,
                                          datanodes=3))
    _dump_metrics(cfg)
    return cfg


def _dump_metrics(cfg: dict) -> None:
    """Drop a /metrics snapshot next to the BENCH_*.json line so perf rounds
    carry drain-batch/codec-batch counters alongside the throughput numbers
    (the raft microbench ran in THIS process, so its drain histogram is in
    the raft role registry; the key counters also ride the JSON configs)."""
    try:
        from chubaofs_tpu.utils import exporter

        raft_stats = exporter.registry("raft").summary(
            "drain_batch", buckets=exporter.BATCH_BUCKETS).snapshot()
        cfg["raft_drain_batches_total"] = raft_stats["count"]
        cfg["raft_drain_entries_total"] = raft_stats["sum"]
        dump_path = os.environ.get("CFS_METRICS_DUMP", "PERF_metrics.prom")
        exporter.dump(dump_path)
        log(f"metrics snapshot -> {dump_path}")
    except Exception as e:  # never kill the bench line over a snapshot
        log(f"metrics snapshot failed: {type(e).__name__}: {e}")


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="cfs-perfbench")
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--files", type=int, default=600)
    p.add_argument("--stream-mb", type=int, default=64)
    p.add_argument("--root", default="")
    args = p.parse_args(argv)

    root = args.root or tempfile.mkdtemp(prefix="cfsperf")
    try:
        cfg = run(root, n_files=args.files, n_clients=args.clients,
                  stream_mb=args.stream_mb)
    finally:
        if not args.root:
            shutil.rmtree(root, ignore_errors=True)
    print(json.dumps({
        "metric": "mdtest_create_ops",
        "value": cfg.get(f"create_ops_{args.clients}c",
                         cfg.get("create_ops_1c", 0.0)),
        "unit": "ops/s",
        "configs": cfg,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
