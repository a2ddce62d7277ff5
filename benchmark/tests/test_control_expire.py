"""test_control.py's whole-run check for the expiry cell (az1.put16m-expire),
its generator kind against a fake gateway, its plain reference against a
hand-worked case and its three reducers against hand-made windows.

Both rehearsals run in a tree of their own beside the real benchmark/: the
cell's floors are a chip window's (2-3 x under the least of them), which a few
seconds of 5 MiB objects on the CPU never reach, so the tree's copy of the
traffic file brings the rehearsal's. The sound rehearsal is correct: every
probe ok, every counter_delta_min floor met, every expire_* metric read. With
`blob_delete` held (the tree's copy of the configuration with
`task_switches_off`) it is not, by the probes (a deleted object still answers
bytes `apply_within_s` after its DELETE) AND by each of the four floors (0
blobs applied, 0 shards deleted, 0 bytes of records made holes, 0 bytes given
back). A program that renders no `require_series` ends the generator before it
says `ready`."""
import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

import closed_put_expire as kind
import reference_expire
from test_control import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "az1.put16m-expire"
with open(os.path.join(BENCH, "configs", "az1-ec12p4-expire.json")) as f:
    CONFIG = json.load(f)
# what a 20 s rehearsal reaches with room; a run with the deleter held reads 0 on each
REHEARSAL_FLOORS = {'cfs_scheduler_delete_blobs{result="ok"}': 200, "cfs_blobnode_shard_delete": 3200,
                    "cfs_blobnode_hole_bytes": 400_000_000, "cfs_blobnode_released_bytes": 400_000_000}
FLOORS = tuple("delta:" + name for name in REHEARSAL_FLOORS)


def run(root, seconds):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483999",
         "--seconds", str(seconds), "--trace", "1", "--rehearse-cpu"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT), capture_output=True,
        text=True, timeout=900)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return p, lines, {l["check"]: l for l in lines if "check" in l}


def rehearsal_tree(tmp_path, held: bool) -> str:
    """A checkout's worth of names around the REAL benchmark/ (every entry a
    link to it), but for two files of its own: the cell's traffic file with
    the rehearsal's floors, and a BENCHMARK.json whose entry for the cell's
    configuration names, with ``held``, a copy of the configuration file with
    `blob_delete` among `task_switches_off` (deploy.py holds what a
    configuration lists there from boot on)."""
    bench_dir = tmp_path / "benchmark"
    (bench_dir / "traffic").mkdir(parents=True)
    for name in os.listdir(BENCH):
        if name not in ("traffic", "__pycache__"):
            os.symlink(os.path.join(BENCH, name), bench_dir / name)
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        os.symlink(os.path.join(BENCH, "traffic", name), bench_dir / "traffic" / name)
    with open(os.path.join(BENCH, "traffic", "put16m-expire.json")) as f:
        traffic = json.load(f)
    assert set(traffic["verify"]["counter_delta_min"]) == set(REHEARSAL_FLOORS)
    traffic["verify"]["counter_delta_min"] = REHEARSAL_FLOORS
    os.unlink(bench_dir / "traffic" / "put16m-expire.json")
    (bench_dir / "traffic" / "put16m-expire.json").write_text(json.dumps(traffic))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if held:
        (tmp_path / "held.json").write_text(json.dumps(dict(CONFIG, task_switches_off=["blob_delete"])))
        next(c for c in bench["configs"] if c["name"] == CONFIG["name"])["file"] = str(tmp_path / "held.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.mark.parametrize("held", [False, True], ids=["sound", "blob_delete_held"])
def test_expire_rehearsal_is_correct_and_not_with_the_deleter_held(tmp_path, held):
    p, lines, checks = run(rehearsal_tree(tmp_path, held), 20 if not held else 10)
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    kinds = {l["kind"] for l in lines if "ops_in_window" in l}
    assert kinds == {"put", "delete", "probe", "probe_live"}
    if not held:
        assert last["correct"] is True and last["failed"] == 0 and all(c["ok"] for c in checks.values())
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            named = {e["name"] for e in json.load(f)["per_layer"] if CELL in e.get("workloads", [])}
        m = {k: v["value"] for k, v in last["metrics"].items()}
        # on the CPU no kernel event is traced: the roofline share finds nothing to read
        assert set(m) == named - {"expire_gf_kernel_roofline"} and len(named) == 27
        assert m["expire_codec_window_compiles"] == 0 and m["expire_reclaimed_per_acked_byte"] >= 0.8
        assert m["expire_backlog_blobs"] <= 3.0 * 44 and m["expire_apply_lag_ms"] <= 3000.0
        assert m["expire_compact_copied_per_reclaimed_byte"] == 0 and m["expire_compact_swap_ms"] == 0
        assert abs(m["expire_stored_growth_pct"]) < 5.0 and m["expire_cpu_reclaim_pct"] > 0
    else:
        assert last["correct"] is False and last["failed"] > 0
        assert checks["failed_ops"]["ok"] is False
        assert [checks[c]["value"] for c in FLOORS] == [0, 0, 0, 0] and not any(checks[c]["ok"] for c in FLOORS)
        failed = [l["failed_op"] for l in lines if "failed_op" in l]
        assert failed and all(o["kind"] == "probe" and "a body of" in o["err"] for o in failed)
        # what verify.py holds for the window's own PUTs stands: they are never deleted
        assert checks["put_objects_mismatched"]["ok"] and checks["shards_differing_from_reference"]["ok"]


# -- the generator against a fake gateway ----------------------------------------------


class Store(http.server.BaseHTTPRequestHandler):
    """PUT /put, POST /get, POST /delete and GET /metrics over a dict.
    `forget`: whether a DELETE takes the object away (a program that
    acknowledges and never applies keeps serving it)."""
    objects: dict = {}
    forget = True
    metrics = "cfs_scheduler_delete_backlog 0\ncfs_blobnode_released_bytes 0\n"
    deletes: list = []
    puts = 0
    lock = threading.Lock()
    protocol_version = "HTTP/1.1"

    def _answer(self, status: int, body: bytes) -> None:
        self.send_response(status)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _serve(self):
        body = self.rfile.read(int(self.headers.get("Content-Length") or 0))
        cls = type(self)
        if self.path == "/metrics":
            return self._answer(200, cls.metrics.encode())
        if self.path == "/put":
            with cls.lock:
                cls.puts += 1  # never reused: a token of a deleted object must stay not-found
                token = json.dumps({"n": cls.puts})
                cls.objects[token] = body
            return self._answer(200, token.encode())
        if self.path == "/delete":
            cls.deletes.append(body.decode())
            if cls.forget:
                cls.objects.pop(body.decode(), None)
            return self._answer(200, b"")
        if self.path == "/get":
            got = cls.objects.get(json.loads(body)["location"])
            return self._answer(404, b"{}") if got is None else self._answer(200, got)
        self._answer(404, b"{}")

    do_GET = do_POST = do_PUT = _serve

    def log_message(self, *a):
        pass


@pytest.fixture()
def store():
    Store.objects, Store.deletes, Store.forget = {}, [], True
    Store.metrics = "cfs_scheduler_delete_backlog 0\ncfs_blobnode_released_bytes 0\n"
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Store)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)


def generator(addr, **params):
    p = {"streams": 2, "object_bytes": 1 << 16, "stagger_s": 0.01, "objects": 1500, "load_streams": 4,
         "apply_within_s": 0.3, "probes_per_s": 20.0, "live_margin": 8,
         "require_series": ["cfs_scheduler_delete_backlog", "cfs_blobnode_released_bytes"]}
    p.update(params)
    gen = kind.Generator({"addr": addr, "seed": 2147483999, "params": p})
    gen.prepare()
    return gen


def ran(gen, seconds=1.2):
    loaded = gen.load()
    assert not loaded["failed"] and len(loaded["locations"]) == gen.p["objects"]
    start = time.monotonic() + 0.05
    return loaded, gen.run(start, start + 0.2, start + seconds)


def test_one_delete_an_acknowledged_put_in_cursor_order(store):
    gen = generator(store)
    loaded, result = ran(gen)
    ops = result["ops"]
    assert {o["kind"] for o in ops} == {"put", "delete", "probe", "probe_live"}  # and none named `get`
    assert all(o["ok"] for o in ops), [o for o in ops if not o["ok"]][:3]
    for s in range(2):
        mine = [o["kind"] for o in ops if o["stream"] == s]
        assert mine[0::2] == ["put"] * len(mine[0::2]) and mine[1::2] == ["delete"] * len(mine[1::2])
        assert len(mine[0::2]) == len(mine[1::2]) > 5
    deletes = sorted((o for o in ops if o["kind"] == "delete"), key=lambda o: o["t_start"])
    assert sorted(o["b"] for o in deletes) == list(range(len(deletes)))  # one shared cursor, no gap, no repeat
    assert sorted(Store.deletes, key=loaded["locations"].index) == loaded["locations"][:len(deletes)]
    assert result["expired"] == len(deletes) and result["loaded"] == 1500
    # only puts carry bytes: no throughput reducer counts a delete or a probe
    assert all(o["bytes"] == (1 << 16 if o["kind"] == "put" else 0) for o in ops)
    probes = [o for o in ops if o["kind"] == "probe"]
    deleted_at = {o["b"]: o["t_end"] for o in deletes}
    assert probes and all(o["t_start"] >= deleted_at[o["b"]] + 0.3 for o in probes)
    live = [o for o in ops if o["kind"] == "probe_live"]
    assert live and all(o["stream"] == 2 and o["a"] == kind.LOAD_A for o in probes + live)
    assert all(o["t_due"] <= o["t_start"] <= o["t_end"] for o in ops)


def test_a_deleted_object_that_still_answers_bytes_is_a_failed_probe(store):
    Store.forget = False
    _, result = ran(generator(store))
    bad = [o for o in result["ops"] if not o["ok"]]
    assert bad and all(o["kind"] == "probe" and o["err"].startswith("a body of 65536 bytes") for o in bad)
    assert all(o["ok"] for o in result["ops"] if o["kind"] == "probe_live")


def test_a_generation_that_does_not_outlast_the_run_is_a_failed_delete(store):
    _, result = ran(generator(store, objects=6, live_margin=1000), seconds=0.8)
    bad = [o for o in result["ops"] if not o["ok"]]
    assert bad and all(o["kind"] == "delete" and "exhausted" in o["err"] for o in bad), \
        [o for o in bad if o["kind"] != "delete"][:3]
    assert result["expired"] == 6 and len(Store.deletes) == 6


@pytest.mark.parametrize("metrics", ["", "cfs_scheduler_delete_backlog 0\n"])
def test_a_program_without_the_reclaim_plane_ends_the_generator_in_prepare(store, tmp_path, metrics):
    Store.metrics = metrics
    with pytest.raises(SystemExit, match="no reclaim plane"):
        generator(store)
    spec = {"addr": store, "kind": "closed_put_expire", "seed": 1, "out": str(tmp_path),
            "params": {"object_bytes": 1 << 16, "streams": 1, "require_series": ["cfs_blobnode_released_bytes"]}}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, os.path.join(BENCH, "loadgen", "child.py"), str(tmp_path / "spec.json")],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and "ready" not in p.stdout and "no reclaim plane" in p.stderr


# -- the plain reference and the reducers ------------------------------------------------


def test_reference_expire_against_a_hand_worked_case():
    # 16 MiB under az1's table: EC12P4, 4 blobs x 16 shards of ceil(4 MiB / 12) = 349,526 B, each a
    # record of 32 + 349,526 + 4 x 6 (six 64 KiB blocks) = 349,582 B
    assert reference_expire.mode_of(16 << 20, CONFIG) == CONFIG["modes"]["EC12P4"]
    assert reference_expire.mode_of(131072, CONFIG) == CONFIG["modes"]["EC3P3"]
    assert reference_expire.record_bytes(349_526, CONFIG["record_framing"]) == 349_582
    assert reference_expire.stored_bytes(16 << 20, CONFIG) == 64 * 349_582 == 22_373_248
    assert reference_expire.stored_bytes(1, CONFIG) == 6 * (32 + 2048 + 4)
    m = reference_expire.Store(CONFIG)
    m.put("a", bytes(range(256)) * 600)  # 153,600 B: EC6P3, 9 shards of 25,600 B, one block each
    assert m.get("a") == bytes(range(256)) * 600 and m.get("b") is None
    assert [s.shape for s in m.stripes("a")] == [(9, 25_600)]
    assert m.stripes("a")[0][:6].tobytes() == bytes(range(256)) * 600  # systematic: the data rows are the object
    assert m.live_stored_bytes() == 9 * (32 + 25_600 + 4)
    m.delete("a")
    assert m.get("a") is None and m.live_stored_bytes() == 0


def reducer(name):
    sys.path.insert(0, BENCH)
    import importlib.util

    spec = importlib.util.spec_from_file_location("reducer_" + name, os.path.join(BENCH, "reducers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def test_the_three_new_reducers_on_hand_made_windows():
    ctx = {"snap0": {"t": 10.0, "stored": 1000, "counters": {"g": 7.0, "p": 100.0, "held": 1000.0}},
           "snap1": {"t": 20.0, "stored": 2000, "counters": {"g": 3.0, "p": 100.0 + 3 * 22_373_248, "held": 1010.0}},
           "ops": [{"kind": "delete", "ok": True, "t_end": t} for t in (9.0, 11.0, 15.0, 19.0, 21.0)]
           + [{"kind": "delete", "ok": False, "t_end": 12.0}, {"kind": "put", "ok": True, "t_end": 12.0}],
           "traffic": {"params": {"object_bytes": 16 << 20}}, "config": CONFIG, "say": lambda **kw: None}
    assert reducer("gauge_at_close")(ctx, {"names": ["g"]}) == 3.0
    assert reducer("gauge_at_close")(ctx, {"names": ["absent"]}) is None
    # the level of what the filesystem holds, not `stored` (the datafiles' lengths, which only grow)
    assert reducer("stored_growth_pct")(ctx, {"gauge": "held"}) == pytest.approx(1.0)
    assert reducer("stored_growth_pct")(ctx, {"gauge": "absent"}) is None
    assert reducer("stored_growth_pct")(dict(ctx, snap0={"counters": {"held": 0.0}}), {"gauge": "held"}) is None
    # three DELETEs acknowledged between the snapshots, three objects' stored bytes released
    assert reducer("released_per_deleted_byte")(ctx, {"kind": "delete", "released": ["p"]}) == pytest.approx(1.0)
    assert reducer("released_per_deleted_byte")(ctx, {"kind": "delete", "released": ["absent"]}) is None
    assert reducer("released_per_deleted_byte")(dict(ctx, ops=[]), {"kind": "delete", "released": ["p"]}) is None
