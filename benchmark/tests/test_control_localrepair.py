"""test_control.py's whole-run check for the local-repair cell
(az2.get16m-localrepair) and its generator kind against a fake gateway.

The sound rehearsal is correct: every body compared, at least 300 shards
rebuilt inside the window and as many decode jobs taken by a LOCAL stripe, a
unit re-homed inside it, no byte read across the AZ boundary; `parity_flip`, a
wrong byte in every codec job, is not, by the bodies the generator compared.
The generator holds disk_repair BEFORE it declares (so the decode warm-up sees
the damage and nothing is rebuilt yet) and releases it at `start`, before the
first GET. A program without POST /admin/disk/set, or one that does not render
cfs_scheduler_rebuild_local_jobs (the parent of the PR that brought the
rebuild by local stripe), ends the generator before it says `ready`; a refused
switch or declaration ends it before any GET: run.py turns either into a
non-zero exit with no result line."""
import json
import os
import subprocess
import sys

import pytest

import closed_get
import closed_get_disk_rebuild as kind
from test_control import ROOT
from test_control_rebuild import Gateway, gateway, set_disk  # noqa: F401  (the fixture)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "az2.get16m-localrepair"


def run(seconds, *extra):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483999",
         "--seconds", str(seconds), "--trace", "1", "--rehearse-cpu", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900)
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    return p, lines, {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("control,seconds", [(None, 30), ("parity_flip", 4)])
def test_localrepair_control_comes_out_not_correct(control, seconds):
    p, lines, checks = run(seconds, *(["--control", control] if control else []))
    assert p.returncode == 0, p.stderr[-2000:]
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["device"]["platform"] == "cpu" and last["platform"] == "cpu"
    warm = next(l["warm_decode"] for l in lines if "warm_decode" in l)
    # the readers' programs from the damage the declarations made, the repair's from the file
    assert warm["shapes"] == ["16+20/want2/262144", "18+1/want1/262144"] and warm["missed"] == {}
    assert warm["named_counts"] == {"18+1/want1/262144": 3}
    if control is None:
        m = {k: v["value"] for k, v in last["metrics"].items()}
        assert last["failed"] == 0
        assert last["correct"] is True and all(c["ok"] for c in checks.values())
        assert checks["delta:cfs_scheduler_repaired_shards"]["value"] >= 300
        assert checks["delta:cfs_scheduler_rebuild_local_jobs"]["value"] >= 300
        assert checks["delta:cfs_scheduler_rebuild_units_committed"]["value"] >= 1
        bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
        named = {e["name"] for e in bench["per_layer"] if CELL in e.get("workloads", [])}
        # every metric of the cell but the kernel's roofline, which needs a device's events
        assert named - set(m) <= {"lrc_gf_kernel_roofline"} and set(m) <= named
        assert m["lrc_rebuild_local_share_pct"] == 100.0 and m["lrc_rebuild_cross_az_read_pct"] == 0.0
        assert m["lrc_rebuild_read_amp"] == pytest.approx(18.0, abs=0.5)
        assert m["lrc_two_round_blobs"] == 0 and m["lrc_codec_window_compiles"] == 0
        assert m["lrc_rebuild_gather_ms"] > 0 and 0 < m["lrc_decoded_share_pct"] <= 12.5
    else:
        assert last["correct"] is False and last["control"] == control
        assert checks["get_bodies_differing"]["ok"] is False


# -- the generator against a fake gateway ------------------------------------------------------

DISKS = [{"disk_id": 1000, "node_id": 1}, {"disk_id": 1001, "node_id": 1},
         {"disk_id": 2000, "node_id": 2}, {"disk_id": 2001, "node_id": 2}]
PARAMS = {"object_bytes": 1 << 16, "declare_broken_disks": [{"node": 1, "nth": 0}, {"node": 2, "nth": 1}]}
METRICS = "# TYPE cfs_scheduler_rebuild_local_jobs counter\ncfs_scheduler_rebuild_local_jobs 0.0\n"


def routes(calls, over=None):
    """A program that can run the cell; every call lands in ``calls``."""
    def told(name, answer):
        def route(path):
            calls.append(name if name != "set" else path.split("?")[1])
            return answer(path) if callable(answer) else answer
        return route

    def switch(path):
        return 200, {"disk_repair": path.endswith("enabled=1")}

    r = {("POST", "/admin/disk/set"): told("set", set_disk), ("GET", "/admin/disks"): told("disks", (200, DISKS)),
         ("POST", "/admin/switch"): lambda path: (calls.append(path.split("?")[1]), switch(path))[1],
         ("GET", "/metrics"): told("metrics", (200, METRICS))}
    r.update(over or {})
    return r


def generator(addr):
    return kind.Generator({"addr": addr, "seed": 1, "params": dict(PARAMS)})


def test_prepare_load_run_call_the_program_in_order(gateway, monkeypatch):
    calls = []
    Gateway.routes = routes(calls)
    monkeypatch.setattr(closed_get.Generator, "load",
                        lambda self: (calls.append("load"), {"failed": [], "locations": []})[1])
    monkeypatch.setattr(closed_get.Generator, "run",
                        lambda self, start, t0, t1: (calls.append("gets"), {"ops": []})[1])
    gen = generator(gateway)
    gen.prepare()
    assert calls == ["disk_id=-1&status=broken", "metrics"]
    del calls[:]
    gen.load()
    # the load on the healthy cluster; the switch held BEFORE the declarations; the nth disk of each node
    assert calls == ["load", "name=disk_repair&enabled=0", "disks",
                     "disk_id=1000&status=broken", "disk_id=2001&status=broken"]
    del calls[:]
    result = gen.run(0.0, 0.0, 0.0)
    assert calls == ["name=disk_repair&enabled=1", "gets"]  # released at start, before the first GET
    assert [a["disk_id"] for a in result["declared"]] == [1000, 2001]


def test_a_failed_load_declares_nothing(gateway, monkeypatch):
    calls = []
    Gateway.routes = routes(calls)
    monkeypatch.setattr(closed_get.Generator, "load", lambda self: {"failed": ["3: put -> 500"], "locations": []})
    assert generator(gateway).load()["failed"] and calls == []


@pytest.mark.parametrize("over,where", [
    ({("POST", "/admin/disk/set"): (404, {"error": "no route"})}, "no POST /admin/disk/set"),
    ({("GET", "/metrics"): (200, "cfs_scheduler_rebuild_decode_jobs 0.0\n")}, "renders no cfs_scheduler_rebuild_local_jobs"),
    ({("GET", "/metrics"): (404, {"error": "no route"})}, "renders no cfs_scheduler_rebuild_local_jobs"),
], ids=["no-declaration-route", "the-parent-no-local-rebuild-series", "no-metrics-route"])
def test_a_program_that_cannot_run_the_cell_ends_the_generator_in_prepare(gateway, over, where):
    Gateway.routes = routes([], over)
    with pytest.raises(SystemExit, match=where):
        generator(gateway).prepare()


@pytest.mark.parametrize("over,where", [
    ({("POST", "/admin/switch"): (400, {"error": "unknown switch"})}, "/admin/switch"),
    ({("POST", "/admin/switch"): (200, {"disk_repair": True})}, "/admin/switch"),  # asked to hold, still on
    ({("POST", "/admin/disk/set"): (409, {"error": "disk 1000 is dropped"})}, "/admin/disk/set"),
    ({("POST", "/admin/disk/set"): (200, {"status": "normal"})}, "/admin/disk/set"),
    ({("GET", "/admin/disks"): (200, DISKS[:1])}, "no disk 1"),
], ids=["switch-refused", "switch-not-held", "declaration-refused", "declaration-not-taken", "no-such-disk"])
def test_a_refused_switch_or_declaration_ends_the_generator_before_any_get(gateway, monkeypatch, over, where):
    Gateway.routes = routes([], over)
    monkeypatch.setattr(closed_get.Generator, "load", lambda self: {"failed": [], "locations": []})
    with pytest.raises(SystemExit, match=where):
        generator(gateway).load()


def test_the_child_ends_before_ready_on_the_parent_so_run_py_prints_no_result(gateway, tmp_path):
    """What run.py sees of the parent commit: it has POST /admin/disk/set and
    no local-rebuild series, the generator process exits non-zero without a
    `ready` line within seconds (Child.expect then raises SystemExit)."""
    Gateway.routes = routes([], {("GET", "/metrics"): (200, "cfs_scheduler_rebuild_decode_jobs 0.0\n")})
    spec = {"addr": gateway, "kind": "closed_get_disk_rebuild", "seed": 1, "out": str(tmp_path),
            "params": dict(PARAMS)}
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, os.path.join(HERE, "..", "loadgen", "child.py"), str(path)],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and "ready" not in p.stdout
    assert "renders no cfs_scheduler_rebuild_local_jobs" in p.stderr
