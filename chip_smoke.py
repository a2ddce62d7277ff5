#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served EC path starts on the chip.

    python chip_smoke.py            # needs one TPU; exit 0 + one JSON line

Drives the blobstore's main path once, through the entry points a user calls,
at the size of a real deployment's stream, and checks every byte:

  kernels  one child process, the chip's only owner while it lives: the five
           models/ registry geometries at their BASELINE.json stripe sizes and
           EC3P3 / EC6P3 / EC12P4 at the 4 MiB access blob, through
           CodecService.encode_tactic / reconstruct_tactic (1 and M missing) /
           decode_rows, once as a single job and once with JOBS jobs in one
           drained batch (group-stacked), byte for byte against numpy
           (ops/gf256). Asserts the lowering that ran is the compiled fused
           Pallas kernel. Reports compilations and compile seconds apart from
           run seconds.
  served   child = the daemon, `python -m chubaofs_tpu.cmd -c blobstore.json`,
           config asking for the TPU, 9 nodes x 2 disks. CLIENTS concurrent
           client streams PUT OBJECTS x OBJECT_MIB objects over HTTP and GET
           them back (sha256); SIGTERM (rc 0), restart on the same root, GET
           again; then one data shard of every stripe is bit-rotted on disk
           (chaos/inject.py), every GET still equal, /metrics shows decoded
           bytes and matmul jobs, the scheduler repairs, a final GET decodes
           nothing. A master daemon whose config ALSO asks for the TPU runs
           beside it: exactly one process of the cluster may map libtpu.
  warm     the kernels child again in a fresh process: compile seconds must
           fall and the compile cache must show hits.
  mesh     only with >= 4 devices: CodecService(mesh=codec_mesh()) compiled on
           the real devices. On one chip it prints "not run", never a pass.

This parent never initialises a JAX backend (it checks that at the end):
children run strictly one after another. Any failed phase makes the exit code
non-zero and names the phase on stderr; no result line is printed. Nothing is
caught and downgraded.

`--rehearse-cpu` is the debugging mode for a sandbox with no chip: tiny sizes,
the CPU backend, and `"platform": "cpu"` stamped on every line it prints. It
is never what the bare command does and it never prints the result line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
MiB = 1 << 20
PHASES = ("kernels", "served", "warm", "mesh")

# the served deployment (ISSUE 21 item 1): upstream's 4 MiB blobs under the
# default single-AZ policy table, so 64 MiB objects take EC12P4 as 16 blobs;
# 18 disks because EC(12,4) places 16 shards; >= 8 streams so drained codec
# batches actually group (g=4 needs >= 4 jobs); >= 1 GiB of user bytes
NODES, DISKS_PER_NODE = 9, 2
OBJECTS, OBJECT_MIB, CLIENTS = 16, 64, 8
PROBE_BYTES = 2 * MiB  # served: a one-blob EC12P4 object, the compile-cache probe
JOBS = 8  # kernels: jobs in flight for the group-stacked pass
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included

REHEARSAL = False  # set by --rehearse-cpu: stamps every printed line


class PhaseFailed(Exception):
    pass


def emit(**rec) -> None:
    if REHEARSAL:
        rec = {"platform": "cpu", **rec}
    print(json.dumps(rec), flush=True)


def check(cond, msg: str) -> None:
    """A smoke assertion that survives `python -O`."""
    if not cond:
        raise PhaseFailed(msg)


def maps_libtpu(pid: int | str) -> bool:
    """Did that process load the TPU runtime? libtpu.so is mapped only when a
    process tries to initialise the TPU backend, never by importing jax."""
    with open(f"/proc/{pid}/maps") as f:
        return any("/libtpu.so" in line for line in f)


# =============================================================================
# children: each is the only process touching the accelerator while it lives
# =============================================================================


def _versions() -> dict:
    import importlib.metadata as md

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            out[pkg] = None
    return out


def _open_device(what: str) -> dict:
    """Place the compile cache, initialise the backend, print what it is."""
    from chubaofs_tpu.ops import device, rs

    cache_dir = device.enable_compile_cache()
    info = device.describe()
    emit(phase=what, device=info, versions=_versions(), compile_cache=cache_dir,
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    if not REHEARSAL:
        import jax

        check(jax.default_backend() == "tpu",
              f"no accelerator: jax.default_backend() is "
              f"{jax.default_backend()!r}, not 'tpu'")
        check(info["lowering"] == rs.FUSED, f"lowering is {info['lowering']}")
    return info


def _reference_stripe(t, data):
    """Plain numpy encode of one stripe, independent of the code under test:
    global RS parity, then (LRC) each AZ's local parity over its own data +
    global-parity rows — the reference's two-stage encode, not the composed
    matrix the service multiplies by."""
    import numpy as np

    from chubaofs_tpu.ops import gf256

    stripe = gf256.encode_numpy(gf256.systematic_generator(t.N, t.M), data)
    if not t.L:
        return stripe
    out = np.zeros((t.total, data.shape[1]), np.uint8)
    out[: t.N + t.M] = stripe
    for idx, local_n, local_m in t.local_stripes():
        gen = gf256.systematic_generator(local_n, local_m)
        out[np.asarray(idx[local_n:])] = gf256.gf_matmul(
            gen[local_n:], stripe[np.asarray(idx[:local_n])])
    return out


def child_kernels(args) -> None:
    import numpy as np

    from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
    from chubaofs_tpu.codec.service import CodecService, bucket_len
    from chubaofs_tpu.models import EC12P4_8M, REGISTRY
    from chubaofs_tpu.ops import device, pallas_gf, rs
    from chubaofs_tpu.utils.exporter import registry

    info = _open_device(args.label)
    shrink = 64 if REHEARSAL else 1  # rehearsal: same widths, short shards
    cases = [(m.name, m.tactic, max(256, m.shard_len // shrink))
             for m in REGISTRY.values()]
    for mode in (CodeMode.EC3P3, CodeMode.EC6P3, CodeMode.EC12P4):
        t = get_tactic(mode)
        cases.append((f"{mode.name.lower()}-4mib-blob", t,
                      max(256, t.shard_size(4 * MiB) // shrink)))

    rng = np.random.default_rng(args.seed)
    single = CodecService()  # the daemon's own defaults
    # max_batch == JOBS: the drain returns the moment all JOBS arrived, so
    # they provably ride ONE batch; the long wait only bounds a lost job
    stacked = CodecService(max_batch=JOBS, max_wait_ms=30_000)
    run_first = run_steady = 0.0
    ops = bytes_checked = 0
    t_phase = time.perf_counter()

    def drive(svc, submit, inputs, want, label):
        """Submit one job per input, compare every output byte; twice — the
        second pass is compile-free, so its seconds are run seconds."""
        nonlocal run_first, run_steady, ops, bytes_checked
        for attempt in ("first", "steady"):
            before = svc.stats_snapshot()
            t0 = time.perf_counter()
            outs = [f.result(timeout=600)
                    for f in [submit(svc, x) for x in inputs]]
            dt = time.perf_counter() - t0
            after = svc.stats_snapshot()
            for got, ref in zip(outs, want):
                check(got.shape == ref.shape and got.dtype == np.uint8
                      and np.array_equal(got, ref),
                      f"{label}: output differs from the numpy reference")
                bytes_checked += ref.size
            if svc is stacked:
                check(after["batches"] - before["batches"] == 1
                      and after["jobs"] - before["jobs"] == len(inputs),
                      f"{label}: {len(inputs)} jobs did not share one batch")
            if attempt == "first":
                run_first += dt
            else:
                run_steady += dt
        ops += 1

    for name, t, k in cases:
        t_case = time.perf_counter()
        datas = [rng.integers(0, 256, (t.N, k), dtype=np.uint8)
                 for _ in range(JOBS + 1)]
        refs = [_reference_stripe(t, d) for d in datas]
        g_total = t.N + t.M
        patterns = [("1miss", [0]),
                    ("Mmiss", sorted(int(i) for i in rng.choice(
                        g_total, t.M, replace=False)))]
        if name == EC12P4_8M.name:  # BASELINE config 4: bulk repair, 3 missing
            patterns.append(("3miss", [0, 5, 12]))
        # windowed decode (ranged degraded read): two lost data rows over a
        # quarter-shard byte window, from exactly N survivors
        want_rows = sorted({1 % t.N, t.N - 1})
        present = [i for i in range(g_total) if i not in want_rows][: t.N]
        lo, hi = k // 4, k // 4 + max(128, k // 4)
        groups = {}
        for svc, sel, tag in ((single, slice(0, 1), "g1"),
                              (stacked, slice(1, JOBS + 1), "stacked")):
            d, r = datas[sel], refs[sel]
            drive(svc, lambda s, x: s.encode_tactic(t, x), d, r,
                  f"{name} encode {tag}")
            for pname, bad in patterns:
                broken = []
                for ref in r:
                    b = ref.copy()
                    b[np.asarray(bad)] = 0xA5  # garbage where shards are lost
                    broken.append(b)
                drive(svc, lambda s, x, bad=bad: s.reconstruct_tactic(t, x, bad),
                      broken, r, f"{name} reconstruct {pname} {tag}")
            drive(svc,
                  lambda s, x: s.decode_rows(t.N, t.M, present, x, want_rows),
                  [ref[np.asarray(present), lo:hi] for ref in r],
                  [ref[np.asarray(want_rows), lo:hi] for ref in r],
                  f"{name} decode_rows {tag}")
            rows8 = 8 * (t.M + t.L)
            groups[tag] = pallas_gf.pick_group(len(d), rows8, 8 * t.N)
        emit(phase=args.label, case=name, N=t.N, M=t.M, L=t.L, shard_len=k,
             bucket=bucket_len(k), encode_group=groups,
             seconds=round(time.perf_counter() - t_case, 3))
    single.close()
    stacked.close()

    # which lowering did the math: the per-lowering job counter AND the jit
    # caches themselves (the einsum must never have been traced)
    reg = registry("codec")
    jobs_total = reg.counter("jobs_total").value
    by_lowering = {lw: reg.counter("lowering_jobs_total", {"lowering": lw}).value
                   for lw in (rs.FUSED, rs.EINSUM)}
    fused_programs = pallas_gf._fused_core._cache_size()
    einsum_programs = rs.gf_matmul_bytes._cache_size()
    if not REHEARSAL:
        check(by_lowering[rs.FUSED] == jobs_total and by_lowering[rs.EINSUM] == 0,
              f"jobs by lowering {by_lowering} != {jobs_total} fused")
        check(fused_programs > 0 and einsum_programs == 0,
              f"jit caches: fused={fused_programs} einsum={einsum_programs}")
    emit(phase=args.label, ok=True, device=info, cases=len(cases), ops=ops,
         jobs=int(jobs_total), bytes_checked=bytes_checked,
         jobs_by_lowering=by_lowering, fused_programs=fused_programs,
         einsum_programs=einsum_programs, **device.compile_stats(),
         first_pass_seconds=round(run_first, 3),
         steady_pass_seconds=round(run_steady, 3),
         wall_seconds=round(time.perf_counter() - t_phase, 3))


def child_mesh(args) -> None:
    """Four chips, one process: the service's mesh path compiled on real
    devices, byte-equal to numpy, every device holding part of the output."""
    import numpy as np

    from chubaofs_tpu.codec.service import CodecService
    from chubaofs_tpu.models import EC12P4_8M, EC20P4L2_16M
    from chubaofs_tpu.ops import device, rs
    from chubaofs_tpu.parallel import codec_mesh, shard_stripes, sharded_gf_matmul

    info = _open_device(args.label)
    n_dev = info["device_count"]
    if n_dev < 4 and not REHEARSAL:
        emit(phase=args.label, ok=True, mesh=f"not run: {n_dev} device")
        return
    mesh = codec_mesh()
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    svc = CodecService(mesh=mesh, max_batch=JOBS, max_wait_ms=30_000)
    rng = np.random.default_rng(args.seed)
    shrink = 64 if REHEARSAL else 1
    for model in (EC12P4_8M, EC20P4L2_16M):
        t, k = model.tactic, max(256, model.shard_len // shrink)
        datas = [rng.integers(0, 256, (t.N, k), dtype=np.uint8)
                 for _ in range(JOBS)]
        outs = [f.result(timeout=600)
                for f in [svc.encode_tactic(t, d) for d in datas]]
        for got, d in zip(outs, datas):
            check(np.array_equal(got, _reference_stripe(t, d)),
                  f"{model.name}: mesh encode differs from numpy")
        emit(phase=args.label, case=model.name, shard_len=k, jobs=JOBS)
    svc.close()
    # placement: the jitted step's own output, before the service gathers it
    run = sharded_gf_matmul(mesh)
    check(REHEARSAL or run.lowering == rs.FUSED, f"mesh lowering {run.lowering}")
    t = EC12P4_8M.tactic
    bits = rs.get_kernel(t.N, t.M).parity_bits
    batch = rng.integers(0, 256, (dp * 2, t.N, sp * 1024), dtype=np.uint8)
    with mesh:
        out = run.jitted(bits, shard_stripes(mesh, batch))
    holders = {s.device.id for s in out.addressable_shards}
    check(len(holders) == n_dev, f"output lives on {holders}, not {n_dev} devices")
    emit(phase=args.label, ok=True, mesh=f"dp={dp} sp={sp}", devices=n_dev,
         output_devices=sorted(holders), lowering=run.lowering,
         **device.compile_stats())


# =============================================================================
# parent: orchestration only — never a JAX backend
# =============================================================================

def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    if REHEARSAL:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def run_child(phase: str, child: str, seed: int, timeout: float) -> dict:
    """One in-process child; its stdout is passed through line by line and
    its last line is its result."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", child,
           "--label", phase, "--seed", str(seed)]
    if REHEARSAL:
        cmd.append("--rehearse-cpu")
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                         env=_child_env(), cwd=HERE)
    killer = threading.Timer(timeout, p.kill)
    killer.start()
    last = ""
    try:
        for line in p.stdout:
            line = line.rstrip("\n")
            if line:
                print(line, flush=True)
                last = line
        rc = p.wait()
    finally:
        killer.cancel()
        if p.poll() is None:  # interrupted mid-phase: leave no process behind
            p.kill()
            p.wait()
    check(rc == 0, f"{phase}: child exited {rc}")
    try:
        res = json.loads(last)
    except ValueError:
        raise PhaseFailed(f"{phase}: child printed no result line") from None
    check(res.get("ok") is True, f"{phase}: child result not ok: {last}")
    return res


def _metrics(addr: str) -> dict[str, float]:
    from chubaofs_tpu.tools import cfsstat

    return cfsstat.parse_metrics(cfsstat.scrape(addr, timeout=60))


def _admin(addr: str, path: str, method: str = "GET"):
    from chubaofs_tpu.rpc.client import RPCClient

    status, _, body = RPCClient([addr], timeout=60).do(method, path)
    check(status == 200, f"{method} {path} -> {status} {body[:200]!r}")
    return json.loads(body)


def _object_bytes(seed: int, i: int, size: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, i]).bytes(size)


def phase_served(seed: int, deadline: float) -> dict:
    from chubaofs_tpu.blobstore.gateway import AccessClient
    from chubaofs_tpu.codec.codemode import CodeMode, get_tactic
    from chubaofs_tpu.testing.harness import ProcCluster, free_port

    ec12p4 = get_tactic(CodeMode.EC12P4)
    objects, obj_size, clients = OBJECTS, OBJECT_MIB * MiB, CLIENTS
    if REHEARSAL:
        objects, obj_size, clients = 4, 9 * MiB, 4  # still multi-blob, EC12P4
    workdir = tempfile.mkdtemp(prefix="cfs-smoke-")
    root = os.path.join(workdir, "blob")
    plat = "cpu" if REHEARSAL else "tpu"
    res: dict = {"objects": objects, "object_bytes": obj_size,
                 "clients": clients, "user_bytes": objects * obj_size}
    # daemons start exactly as an operator starts them — `python -m
    # chubaofs_tpu.cmd -c cfg.json` — through the harness's spawn/boot-line
    # machinery. One process per chip: beside the blobstore runs a master
    # whose config ALSO asks for the TPU (what a launcher handing one platform
    # to every role does); cmd.py pins every non-blobstore role to the CPU.
    cluster = ProcCluster.shell(
        workdir, env={"JAX_PLATFORMS": "cpu"} if REHEARSAL else None)
    master_cfg = {
        "role": "master", "id": 1, "jaxPlatform": plat,
        "raftPeers": {"1": f"127.0.0.1:{free_port()}"},
        "listen": "127.0.0.1:0", "walDir": os.path.join(workdir, "m1")}
    blob_cfg = {
        "role": "blobstore", "root": root, "listen": "127.0.0.1:0",
        "nodes": NODES, "disksPerNode": DISKS_PER_NODE, "jaxPlatform": plat}
    blob: dict = {}  # the running blobstore: {"proc": Popen, "boot": {...}}

    def stop(proc: subprocess.Popen, name: str) -> None:
        """SIGTERM = the graceful stop; exit code 0 is part of the check."""
        proc.send_signal(signal.SIGTERM)
        check(proc.wait(timeout=60) == 0,
              f"{name} exited {proc.returncode} on SIGTERM")

    def boot_blobstore() -> str:
        t0 = time.perf_counter()
        blob["proc"] = cluster.spawn("blobstore", dict(blob_cfg))
        boot = blob["boot"] = cluster.boot_info("blobstore", timeout=300)
        for key in ("platform", "device_kind", "device_count", "lowering",
                    "kv_engine", "frame_engine"):
            check(key in boot, f"boot line lacks {key!r}: {boot}")
        check(boot["platform"] == plat, f"boot line platform: {boot}")
        stat = _admin(boot["addr"], "/admin/stat")
        check(stat["device"] == {k: boot[k] for k in stat["device"]},
              f"/admin/stat device {stat['device']} != boot line {boot}")
        emit(phase="served", boot=boot, disks=stat["disks"],
             boot_seconds=round(time.perf_counter() - t0, 3))
        check(stat["disks"] == NODES * DISKS_PER_NODE, f"disks: {stat}")
        return boot["addr"]

    def client_for(addr: str) -> AccessClient:
        # every boot binds a fresh port, so no pooled connection is stale; a
        # timeout long enough that a PUT stuck behind cold compiles is never
        # resent (PUT is not idempotent: a resend stores the object twice)
        c = AccessClient([addr])
        c.rpc.timeout = max(60.0, deadline - time.monotonic())
        return c

    def get_all(addr: str, locs: dict, what: str) -> float:
        client = client_for(addr)

        def one(i: int) -> None:
            body = client.get(locs[i][0])
            check(hashlib.sha256(body).hexdigest() == locs[i][1],
                  f"{what}: object {i} read back different bytes")

        t0 = time.perf_counter()
        with ThreadPoolExecutor(clients) as pool:
            list(pool.map(one, sorted(locs)))
        dt = time.perf_counter() - t0
        emit(phase="served", step=what, objects=len(locs),
             wall_seconds=round(dt, 3))
        return dt

    def codec_counters(addr: str) -> dict:
        m = _metrics(addr)
        return {
            "decoded_bytes": m.get('cfs_access_read_bytes{kind="decoded"}', 0.0),
            "matmul_jobs": m.get('cfs_codec_kind_jobs_total{kind="matmul"}', 0.0),
            "encode_jobs": m.get('cfs_codec_kind_jobs_total{kind="encode"}', 0.0),
            "fused_jobs": m.get(
                'cfs_codec_lowering_jobs_total{lowering="pallas-fused"}', 0.0),
            "einsum_jobs": m.get(
                'cfs_codec_lowering_jobs_total{lowering="xla-einsum"}', 0.0),
            "batches": m.get("cfs_codec_batches_total", 0.0),
            "jobs": m.get("cfs_codec_jobs_total", 0.0),
            "compiles": m.get("cfs_codec_compile_total", 0.0),
            "compile_seconds": m.get("cfs_codec_compile_seconds_total", 0.0),
            "cache_hits": m.get("cfs_codec_compile_cache_hits_total", 0.0),
            "cache_writes": m.get("cfs_codec_compile_cache_writes_total", 0.0),
            "repaired_shards": m.get("cfs_scheduler_repaired_shards", 0.0),
        }

    try:
        master = cluster.spawn("master1", master_cfg)
        cluster.boot_info("master1", timeout=300)
        addr = boot_blobstore()
        res["kv_engine"] = blob["boot"]["kv_engine"]
        res["frame_engine"] = blob["boot"]["frame_engine"]
        res["device"] = {k: blob["boot"][k] for k in
                         ("platform", "device_kind", "device_count", "lowering")}
        # -- one owner per chip, seen from outside the processes ------------
        owners = {"master1": maps_libtpu(master.pid),
                  "blobstore": maps_libtpu(blob["proc"].pid)}
        emit(phase="served", libtpu_mapped=owners)
        if not REHEARSAL:
            check(owners == {"master1": False, "blobstore": True},
                  f"processes that loaded the TPU runtime: {owners}")

        # -- PUT: concurrent client streams ---------------------------------
        locs: dict[int, tuple[str, str]] = {}  # i -> (location token, sha256)

        def put_one(client: AccessClient, i: int, size: int = obj_size) -> None:
            body = _object_bytes(seed, i, size)
            loc = client.put(body)
            locs[i] = (loc.to_json(), hashlib.sha256(body).hexdigest())

        client = client_for(addr)
        t0 = time.perf_counter()
        with ThreadPoolExecutor(clients) as pool:
            list(pool.map(lambda i: put_one(client, i), range(objects)))
        res["put_wall_seconds"] = round(time.perf_counter() - t0, 3)
        cold = codec_counters(addr)
        emit(phase="served", step="put", objects=objects,
             wall_seconds=res["put_wall_seconds"], codec=cold)
        modes = {json.loads(tok)["code_mode"] for tok, _ in locs.values()}
        check(modes == {int(CodeMode.EC12P4)},
              f"objects did not take EC12P4: code modes {modes}")
        check(cold["encode_jobs"] >= objects * (obj_size // (4 * MiB)),
              f"too few encode jobs: {cold}")
        check(REHEARSAL or (cold["einsum_jobs"] == 0
                            and cold["fused_jobs"] == cold["jobs"]),
              f"served jobs by lowering: {cold}")
        res["daemon_cold"] = cold
        # the cache probe: one 2 MiB object PUT alone is exactly one encode
        # job in a batch of one — one program, the same in every process
        put_one(client, objects, PROBE_BYTES)
        get_all(addr, locs, "get")
        # reported, not asserted: a direct shard read that misses access's 3 s
        # read deadline on a busy host is decoded around, by design
        res["healthy_get_decoded_bytes"] = codec_counters(addr)["decoded_bytes"]

        # -- acknowledged writes survive a restart --------------------------
        stop(blob["proc"], "blobstore")
        addr = boot_blobstore()
        get_all(addr, locs, "get-after-restart")
        # ...and the restarted process finds the probe's program in the
        # compile cache instead of compiling it
        put_one(client_for(addr), objects + 1, PROBE_BYTES)
        warm = codec_counters(addr)
        emit(phase="served", step="probe-put-after-restart", codec=warm)
        check(warm["cache_hits"] >= 1
              and (warm["cache_writes"] == 0 or warm["matmul_jobs"] > 0),
              f"restarted daemon did not take the probe's program from the "
              f"compile cache: {warm}")
        res["daemon_restarted"] = warm

        # -- degraded: bit-rot one data shard of EVERY stripe ----------------
        # repair is switched off (persisted in clustermgr) so the scrub cannot
        # heal the damage before a client has read through it
        _admin(addr, "/admin/switch?name=shard_repair&enabled=0", "POST")
        targets = []  # (node_id, vuid, bid)
        units: dict[int, list] = {}
        stripe_i = 0
        for tok, _ in locs.values():
            for b in json.loads(tok)["blobs"]:
                if b["vid"] not in units:
                    units[b["vid"]] = _admin(
                        addr, f"/admin/volume?vid={b['vid']}")["units"]
                u = units[b["vid"]][stripe_i % ec12p4.N]  # a DATA unit
                targets.append((u["node_id"], u["vuid"], b["bid"]))
                stripe_i += 1
        stop(blob["proc"], "blobstore")
        _bitrot(root, targets)
        addr = boot_blobstore()
        before = codec_counters(addr)
        get_all(addr, locs, "get-degraded")
        after = codec_counters(addr)
        emit(phase="served", step="degraded", damaged_shards=len(targets),
             codec=after)
        check(after["decoded_bytes"] > before["decoded_bytes"],
              f"degraded GET decoded nothing: {after}")
        check(after["matmul_jobs"] > before["matmul_jobs"],
              f"degraded GET ran no matmul jobs: {after}")
        check(REHEARSAL or after["einsum_jobs"] == 0, f"einsum ran: {after}")
        res["damaged_shards"] = len(targets)
        res["degraded"] = after

        # -- repair: background ticks until the damage is gone ---------------
        _admin(addr, "/admin/switch?name=shard_repair&enabled=1", "POST")
        t0 = time.perf_counter()
        while True:
            check(time.monotonic() < deadline, "repair did not finish in time")
            time.sleep(2.0)
            tasks = [t for t in _admin(addr, "/admin/tasks")
                     if t["kind"] == "shard_repair"]
            done = sum(t["state"] == "finished" for t in tasks)
            pending = sum(t["state"] in ("prepared", "working") for t in tasks)
            failed = [t for t in tasks if t["state"] == "failed"]
            check(not failed, f"repair tasks failed: {failed[:3]}")
            base = codec_counters(addr)
            if not done or pending or base["repaired_shards"] < len(targets):
                continue
            get_all(addr, locs, "get-after-repair")
            if codec_counters(addr)["decoded_bytes"] == base["decoded_bytes"]:
                break  # a full read of every object decoded nothing
        res["repair_tasks_finished"] = done
        res["repair_wall_seconds"] = round(time.perf_counter() - t0, 3)
        res["daemon_final"] = codec_counters(addr)
        stop(blob["proc"], "blobstore")
        stop(master, "master1")
    except BaseException:
        for name in ("blobstore", "master1"):
            log = os.path.join(workdir, f"{name}.log")
            if os.path.exists(log):
                with open(log, errors="replace") as f:
                    print(f"--- {name} log tail ---\n{f.read()[-3000:]}",
                          file=sys.stderr, flush=True)
        raise
    finally:
        cluster.close()  # whatever still runs: terminate, then kill
        shutil.rmtree(workdir, ignore_errors=True)
    emit(phase="served", ok=True, **res)
    return res


def _bitrot(root: str, targets: list[tuple[int, int, int]]) -> None:
    """Flip a payload byte of each (node, vuid, bid) shard under the CRC
    framing, with the daemon down — chaos/inject.py, the repo's own lever."""
    from chubaofs_tpu.blobstore.blobnode import BlobNode
    from chubaofs_tpu.chaos.inject import corrupt_shard_on_disk

    by_node: dict[int, list] = {}
    for node_id, vuid, bid in targets:
        by_node.setdefault(node_id, []).append((vuid, bid))
    for node_id, shards in by_node.items():
        node = BlobNode(node_id, [
            os.path.join(root, f"node{node_id}", f"disk{d}")
            for d in range(DISKS_PER_NODE)])
        try:
            for vuid, bid in shards:
                corrupt_shard_on_disk(node, vuid, bid)
        finally:
            node.close()


def main() -> int:
    global REHEARSAL
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="all data is generated from this seed")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list, a subset of %(default)s (debugging; "
                         "the result line needs all of them)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox debugging: tiny sizes on the CPU backend, "
                         'every line stamped "platform": "cpu", no result line')
    ap.add_argument("--child", choices=("kernels", "mesh"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--label", help=argparse.SUPPRESS)  # phase name to print
    args = ap.parse_args()
    REHEARSAL = args.rehearse_cpu
    if args.child:
        args.label = args.label or args.child
        {"kernels": child_kernels, "mesh": child_mesh}[args.child](args)
        return 0

    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}")
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    summary: dict = {"seed": args.seed, "phases": {}}
    phase = "start"
    try:
        for phase in [p for p in PHASES if p in phases]:
            t0 = time.monotonic()
            left = deadline - t0
            check(left > 30, f"out of time before {phase}")
            if phase == "kernels":
                out = run_child(phase, "kernels", args.seed, left)
            elif phase == "served":
                out = phase_served(args.seed, deadline)
            elif phase == "warm":
                out = run_child(phase, "kernels", args.seed, left)
                cold = summary["phases"].get("kernels")
                check(out["cache_hits"] >= 1,
                      f"warm: no compile-cache hits: {out}")
                if cold and cold["cache_writes"] > 0:
                    check(out["compile_seconds"] < cold["compile_seconds"],
                          f"warm: compile seconds did not fall: "
                          f"{cold['compile_seconds']} -> {out['compile_seconds']}")
            else:
                dev = (summary["phases"].get("kernels") or {}).get("device")
                if dev and dev["device_count"] < 4 and not REHEARSAL:
                    out = {"ok": True,
                           "mesh": f"not run: {dev['device_count']} device"}
                    emit(phase="mesh", **out)
                else:
                    out = run_child(phase, "mesh", args.seed, left)
            out["phase_seconds"] = round(time.monotonic() - t0, 3)
            summary["phases"][phase] = out
        # the parent stayed off the chip, start to end
        check(not maps_libtpu("self"), "the smoke's parent loaded libtpu")
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            check(not xla_bridge._backends,
                  f"the smoke's parent initialised {list(xla_bridge._backends)}")
    except BaseException as e:
        # name the phase, whatever failed; nothing is downgraded to a pass
        print(f"chip_smoke FAILED in phase {phase!r}: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        if isinstance(e, PhaseFailed):
            return 1
        raise
    summary["wall_seconds"] = round(time.monotonic() - t_start, 3)
    emit(summary=summary)
    if REHEARSAL or set(phases) != set(PHASES):
        emit(ok=False, note="partial or rehearsal run: no result line")
        return 0
    dev = summary["phases"]["kernels"]["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
