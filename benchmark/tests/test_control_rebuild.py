"""test_control.py's whole-run check for the rebuild cell (az1.get16m-rebuild):
the sound rehearsal is correct (every body compared, at least 300 shards
rebuilt inside the window, the rebuild still going when it opens), and
`parity_flip`, a wrong byte in every codec job, is not, by the bodies the
generator compared: the rebuild's rows come from the same jobs, so healed
shards read back wrong too. A program that lacks POST /admin/disk/set, or
refuses the declaration, ends the generator before it says `ready` / before any
GET, which run.py turns into a non-zero exit with no result line."""
import http.server
import json
import os
import subprocess
import sys
import threading

import pytest

import closed_get_rebuild
from test_control import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


def run(seconds, *extra):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "az1.get16m-rebuild", "--seed", "2147483999",
         "--seconds", str(seconds), "--trace", "0", "--rehearse-cpu", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return p, lines[-1], {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("control,seconds", [(None, 30), ("parity_flip", 4)])
def test_rebuild_control_comes_out_not_correct(control, seconds):
    p, last, checks = run(seconds, *(["--control", control] if control else []))
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    assert "get_MBps" in last["metrics"] and "setup_s" in last["metrics"]
    if control is None:
        assert last["failed"] == 0
        assert last["correct"] is True and all(c["ok"] for c in checks.values())
        assert checks["delta:cfs_scheduler_repaired_shards"]["value"] >= 300
        # a unit re-homed inside the window: the GETs after it read its rebuilt rows, and are compared
        assert checks["delta:cfs_scheduler_rebuild_units_committed"]["value"] >= 1
    else:
        assert last["correct"] is False and last["control"] == control
        assert checks["get_bodies_differing"]["ok"] is False


class Gateway(http.server.BaseHTTPRequestHandler):
    """A gateway that knows /admin/disks and answers /admin/disk/set as told."""
    routes: dict = {}
    protocol_version = "HTTP/1.1"

    def _serve(self):
        self.rfile.read(int(self.headers.get("Content-Length") or 0))
        status, body = 404, {"error": "no route"}
        for (method, prefix), answer in self.routes.items():
            if self.command == method and self.path.startswith(prefix):
                status, body = answer(self.path) if callable(answer) else answer
                break
        raw = json.dumps(body).encode()
        self.send_response(status)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    do_GET = do_POST = _serve

    def log_message(self, *a):
        pass


@pytest.fixture()
def gateway():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Gateway)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)


DISKS = [{"disk_id": 1000, "node_id": 1}, {"disk_id": 1001, "node_id": 1}, {"disk_id": 2000, "node_id": 2}]


def set_disk(path):
    if "disk_id=-1" in path:
        return 404, {"error": "unknown disk -1"}
    disk = int(path.split("disk_id=")[1].split("&")[0])
    return 200, {"disk_id": disk, "status": "broken", "was": "normal", "tasks": ["t1"]}


def test_declaration_names_the_nodes_disks(gateway):
    Gateway.routes = {("GET", "/admin/disks"): (200, DISKS), ("POST", "/admin/disk/set"): set_disk}
    got = closed_get_rebuild.declare_broken(gateway, [1])
    assert [a["disk_id"] for a in got] == [1000, 1001]


@pytest.mark.parametrize("routes,where", [
    ({}, "no POST /admin/disk/set"),                                           # the parent: no route
    ({("POST", "/admin/disk/set"): (404, {"error": "no route"})}, "no POST /admin/disk/set"),
])
def test_a_program_without_the_call_ends_the_generator_in_prepare(gateway, routes, where):
    Gateway.routes = routes
    gen = closed_get_rebuild.Generator({"addr": gateway, "seed": 1, "params": {"object_bytes": 1 << 16}})
    with pytest.raises(SystemExit, match=where):
        gen.prepare()


@pytest.mark.parametrize("answer", [(409, {"error": "disk 1000 is dropped"}), (200, {"status": "normal"}),
                                    (500, {"error": "boom"})])
def test_a_refused_declaration_ends_the_generator_before_any_get(gateway, answer):
    Gateway.routes = {("GET", "/admin/disks"): (200, DISKS), ("POST", "/admin/disk/set"): answer}
    with pytest.raises(SystemExit, match="/admin/disk/set"):
        closed_get_rebuild.declare_broken(gateway, [1])


def test_the_child_ends_before_ready_so_run_py_prints_no_result(gateway, tmp_path):
    """What run.py sees of a program without the call: the generator process
    exits non-zero without a `ready` line (Child.expect then raises SystemExit:
    non-zero exit, inside set-up, no result line)."""
    Gateway.routes = {}
    spec = {"addr": gateway, "kind": "closed_get_rebuild", "seed": 1, "out": str(tmp_path),
            "params": {"object_bytes": 1 << 16, "declare_broken_nodes": [1]}}
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, os.path.join(HERE, "..", "loadgen", "child.py"), str(path)],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and "ready" not in p.stdout
    assert "no POST /admin/disk/set" in p.stderr
