"""One cell of the benchmark per process, on the chip, through the served path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process owns the chip: it boots the cell's deployment in-process
(benchmark/deploy.py), starts the load generator as a child that never imports
jax (benchmark/loadgen/child.py), warms every compiled program the cell can
reach, measures for --seconds, checks what the window produced
(benchmark/verify.py) and prints one JSON object as its last line. Everything
about a cell comes from files found by name: BENCHMARK.json names the cell's
configuration, traffic mix and metrics; benchmark/configs, traffic, loadgen,
endtoend, layers and reducers hold them. Nothing here names one."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import window as win  # noqa: E402  (benchmark/window.py)

# The measured window's design, the same in every cell (PERF.md, Findings PR 24):
WARM_SECONDS = 5.0  # of the cell's own traffic before the window opens
GENERATOR_CORE_SHARE = 0.3  # of this process's cores go to the generator, the rest to the daemon


def say(**kw) -> None:
    """A diagnostic line: everything but the last line of stdout."""
    print(json.dumps(kw), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_reducer(name: str):
    path = os.path.join(HERE, "reducers", name + ".py")
    spec = importlib.util.spec_from_file_location("reducer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def meminfo() -> dict:
    want = ("Dirty", "Writeback", "MemAvailable")
    with open("/proc/meminfo") as f:
        return {k: v.strip() for k, _, v in (l.partition(":") for l in f) if k in want}


def filesystem(path: str) -> dict:
    path = os.path.realpath(path)
    st = os.statvfs(path)
    best = ("", "?")
    with open("/proc/mounts") as f:
        for line in f:
            _, mnt, fstype = line.split()[:3]
            if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) and len(mnt) > len(best[0]):
                best = (mnt, fstype)
    return {"mount": best[0], "type": best[1], "free_bytes": st.f_bavail * st.f_frsize}


def split_cores(share: float) -> tuple[list[int], list[int]]:
    """Disjoint core sets: the last ``share`` of this process's cores (at
    least one, never all) go to the generator, the rest stay with the daemon."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 2:
        return cores, cores
    n_gen = min(len(cores) - 1, max(1, round(len(cores) * share)))
    return cores[:-n_gen], cores[-n_gen:]


class Child:
    """The generator process and its line protocol."""

    def __init__(self, spec: dict, out: str):
        path = os.path.join(out, "spec.json")
        with open(path, "w") as f:
            json.dump(spec, f)
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX", "XLA", "TPU"))}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen", "child.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)

    def expect(self, event: str) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise SystemExit(f"the generator ended (rc {self.proc.wait()}) before {event!r}")
        msg = json.loads(line)
        if msg.get("event") != event:
            raise SystemExit(f"the generator said {msg} where {event!r} was due")
        return msg

    def go(self, **kw) -> None:
        self.proc.stdin.write(json.dumps(kw) + "\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def sleep_until(t: float) -> None:
    while (d := t - time.monotonic()) > 0:
        time.sleep(d)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox only: CPU, stamped on every line; never a result")
    ap.add_argument("--control", default=None,
                    help="output check only: break one guarantee (benchmark/controls.py)")
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    config = load_json(ROOT, next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    stamp = {}
    if args.control:
        stamp["control"] = args.control
    if args.rehearse_cpu:
        stamp["platform"] = "cpu"
        traffic["params"].update(traffic.get("rehearse_params", {}))
    if stamp:
        say(**stamp)

    run_dir = os.path.join(ROOT, ".bench_run", args.workload)
    shutil.rmtree(run_dir, ignore_errors=True)  # a clean start: no earlier run's data
    out, data_root = os.path.join(run_dir, "out"), os.path.join(run_dir, "data")
    os.makedirs(out)
    os.makedirs(data_root)

    warm_s = WARM_SECONDS
    fs = filesystem(data_root)
    need = traffic["disk_bytes_per_s"] * (warm_s + args.seconds) + traffic.get("load_disk_bytes", 0)
    say(data_root=data_root, filesystem=fs, disk_bytes_needed=need)
    if fs["free_bytes"] < need and not args.rehearse_cpu:
        raise SystemExit(f"free space {fs['free_bytes']} is below what a window writes ({need})")

    daemon_cores, gen_cores = split_cores(GENERATOR_CORE_SHARE)
    os.sched_setaffinity(0, daemon_cores)  # before jax starts its threads
    say(cores={"daemon": daemon_cores, "generator": gen_cores})

    import deploy

    dep = deploy.Deployment(config, data_root, "cpu" if args.rehearse_cpu else "tpu")
    child = None
    try:
        device = dep.device()
        if not args.rehearse_cpu and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
            raise SystemExit(f"cell needs {cell['chips']} TPU chip(s), found {device}")
        peaks = load_json(HERE, "peaks.json").get(device["kind"])
        if peaks is None and not args.rehearse_cpu:
            raise SystemExit(f"no peaks for device kind {device['kind']!r} in benchmark/peaks.json")
        say(boot=dep.boot_info, addr=dep.addr, compile_cache=dep.cache_dir,
            boot_s=time.monotonic() - T_PROCESS)
        if args.control:
            import controls

            controls.apply(args.control, dep, args.seed)

        child = Child({"addr": dep.addr, "kind": traffic["kind"], "seed": args.seed,
                       "params": traffic["params"], "out": out, "cores": gen_cores,
                       "warm_s": warm_s, "seconds": args.seconds}, out)
        child.expect("ready")
        streams = traffic["params"].get("streams")
        c0 = dep.counters()
        warm = traffic["warm"]
        if warm.get("encode") or warm.get("encode_shapes"):
            sizes = traffic["params"].get("sizes") or [traffic["params"]["object_bytes"]]
            blobs = max(-(-s // config["max_blob_size"]) for s in sizes)
            say(warm_encode=dep.warm_encode(sizes if warm.get("encode") else [],
                                            streams * blobs if streams else 1 << 30,
                                            warm.get("encode_shapes", ())),
                at_s=time.monotonic() - T_PROCESS)
        loaded = None
        if traffic.get("loads"):
            child.go(cmd="load")
            say(loaded=child.expect("loaded"), at_s=time.monotonic() - T_PROCESS)
            loaded = load_json(out, "load.json")
            if loaded["failed"]:
                raise SystemExit(f"set-up load failed: {loaded['failed'][:3]}")
        if traffic.get("nodes_down"):
            dep.node_down(traffic["nodes_down"])
        if traffic.get("switches_off"):
            dep.switch_off(traffic["switches_off"])
        if warm.get("decode") or warm.get("decode_shapes"):
            say(warm_decode=dep.warm_decode(loaded["locations"] if warm.get("decode") else [],
                                            streams * dep.gather_window(), warm.get("decode_shapes", ())),
                at_s=time.monotonic() - T_PROCESS)
        c1 = dep.counters()
        say(setup_compiles={k: c1.get(k, 0) - c0.get(k, 0) for k in (
            "cfs_codec_compile_total", "cfs_codec_compile_seconds_total",
            "cfs_codec_compile_cache_hits_total")})
        os.sync()  # this run's own load and warm-up are on disk before any traffic

        # -- warm-up by the cell's own traffic, then the window -------------
        start = time.monotonic() + 0.25
        t0 = start + warm_s
        t1 = t0 + args.seconds
        child.go(start=start, t0=t0, t1=t1)
        trace_dir = os.path.join(out, "trace")
        sleep_until(t0 - (0.6 if args.trace else 0.0))
        if args.trace:
            import jax.profiler as prof

            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0  # host Python frames of a whole window would swamp the trace
            opts.host_tracer_level = 2
            t_trace = time.monotonic()  # the trace's clock starts here
            prof.start_trace(trace_dir, profiler_options=opts)
            sleep_until(t0)
            annotation = prof.TraceAnnotation("bench:window")
            annotation.__enter__()
        snap0 = {"t": time.monotonic(), "counters": dep.counters(), "stored": dep.stored_bytes(),
                 "meminfo": meminfo()}
        setup_s = snap0["t"] - T_PROCESS
        sleep_until(t1)
        snap1 = {"t": time.monotonic(), "counters": dep.counters(), "stored": dep.stored_bytes(),
                 "meminfo": meminfo()}
        if args.trace:
            annotation.__exit__(None, None, None)
            prof.stop_trace()
        child.expect("done")
        result = load_json(out, "ops.json")
        ops = result["ops"]

        # -- the window, on earlier lines ---------------------------------------
        compiles = snap1["counters"].get("cfs_codec_compile_total", 0) - \
            snap0["counters"].get("cfs_codec_compile_total", 0)
        say(window_compiles=compiles,
            warm_traffic_compiles=snap0["counters"].get("cfs_codec_compile_total", 0)
            - c1.get("cfs_codec_compile_total", 0))
        say(meminfo={"window_start": snap0["meminfo"], "window_end": snap1["meminfo"]},
            filesystem_end=filesystem(data_root))
        timeline = win.timeline(ops, t0, t1)
        say(timeline_bytes_per_s=timeline,
            seconds_under_half_the_median=sum(1 for b in timeline if b < sorted(timeline)[len(timeline) // 2] / 2))
        for kind in sorted({o["kind"] for o in ops}):
            c2c = win.c2c_bytes_per_s(ops, t0, t1, kind)
            say(kind=kind, ops_in_window=len(win.in_window(ops, t0, t1, kind)),
                fixed_window_MBps=win.fixed_window_bytes_per_s(ops, t0, t1, kind) / 1e6,
                c2c_MBps=None if c2c is None else c2c / 1e6)
        if result.get("lateness_ms"):
            say(generator_lateness_ms=result["lateness_ms"])
        if result.get("compare_ms"):
            # what the generator holds its own interpreter lock for, a GET: at a share
            # near 1 the cell reads its generator and not the server (PERF.md, Findings PR 41)
            gets_per_s = len(win.in_window(ops, t0, t1, "get")) / args.seconds
            say(generator_compare_ms=result["compare_ms"],
                generator_turnaround_ms=result.get("turnaround_ms"), gets_per_s=gets_per_s,
                compare_share_of_one_lock=gets_per_s * result["compare_ms"]["mean"] / 1e3)
        if compiles:
            # the warm-up did not cover what this window reached: its numbers hold
            # compilation, so there is no result (PERF.md, Findings PR 24: mechanism 1)
            raise SystemExit(f"{compiles:g} codec program(s) compiled inside the measured window: no result")

        # -- metrics, each by the reducer its file names -----------------------
        trace = None
        if args.trace:
            import xplane

            path = xplane.find_trace(trace_dir)
            if path is None:
                raise SystemExit("the profiler wrote no trace")
            trace = xplane.load(path)
            if xplane.window_of(trace, "bench:window") is None:
                # the profiler lost the annotation (once in nine traced runs): place
                # the window by the host clock, good to start_trace's own latency
                trace["annotations"].append(("bench:window", t0 - t_trace, t1 - t_trace))
                say(trace_window="bench:window annotation missing from the trace; placed by the host clock")
            shutil.copy(path, os.path.join(out, "window.xplane.pb"))
        ctx = {"ops": ops, "t0": t0, "t1": t1, "seed": args.seed, "setup_s": setup_s,
               "snap0": snap0, "snap1": snap1, "trace": trace, "peaks": peaks,
               "config": config, "traffic": traffic, "say": say}
        group, folder = ("per_layer", "layers") if args.trace else ("end_to_end", "endtoend")
        metrics = {}
        for m in bench[group]:
            if "workloads" in m and args.workload not in m["workloads"]:
                continue
            spec = load_json(HERE, folder, m["name"] + ".json")
            value = load_reducer(spec["reducer"])(ctx, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        # -- correct ---------------------------------------------------------------
        import verify

        t_v = time.monotonic()
        checks = {}

        def say_check(**kw) -> None:
            if "check" in kw:
                checks[kw["check"]] = {k: kw[k] for k in ("value", "limit", "ok")}
            say(**kw)

        correct, attempted, failed = verify.decide(
            dep, config, traffic, result, t0, t1, args.seed,
            (snap0["counters"], snap1["counters"]), say_check)
        say(verify_s=time.monotonic() - t_v, total_s=time.monotonic() - T_PROCESS)
        device = dep.device()
        line = {"correct": bool(correct), "attempted": attempted, "failed": failed,
                "metrics": metrics, "device": device}
        if trace is not None:
            summary = xplane.device_summary(trace, "bench:window")
            line["device"].update(summary["device"])
            line["breakdown"] = summary["breakdown"]
        line.update(stamp)
        line["checks"] = checks  # every number compared beside its limit: last in the line
    finally:
        if child is not None:
            child.close()
        try:
            dep.stop()
        except Exception as e:  # the result stands; a slow teardown is said on stderr
            print(f"teardown: {type(e).__name__}: {e}", file=sys.stderr)
        shutil.rmtree(data_root, ignore_errors=True)
    for name, c in line["checks"].items():  # and the last lines of stderr
        print(f"check {name}: {c['value']} {c['limit']} ok={c['ok']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
