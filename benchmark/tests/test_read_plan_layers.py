"""get_blob_read_ms and get_one_round_share (data files over stage_ms and
counter_ratio) reduce on a CPU rehearsal window of each degraded-GET cell (which
also prints the generator's own compare and turnaround on a diagnostic line), and
on the counters of a program that has no cfs_access_read_plan_total (the parent
of the PR that added it) the share reads nothing while the stage metric reads
both of that program's rounds."""
import json
import os
import subprocess
import sys

import pytest

from run import load_reducer
from test_control import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
FROZEN = ["az1.get16m-nodedown", "az2.get16m-azdown"]  # the damage stands still: every blob one round
GET_CELLS = FROZEN + ["az1.get16m-rebuild"]  # PR 41: its share falls as units are re-homed


def layer(name):
    with open(os.path.join(HERE, "..", "layers", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", GET_CELLS)
def test_both_reduce_on_a_rehearsal_window(cell):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "2147484035",
         "--seconds", "6", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.splitlines() if l.startswith("{")]
    last = lines[-1]
    assert last["platform"] == "cpu" and last["failed"] == 0
    m = last["metrics"]
    # every stripe of the frozen-damage deployments has a data unit on what is down; under the
    # rebuild a re-homed unit's blobs go back to the direct plan (none need be, in 6 s on the CPU)
    share = m["get_one_round_share"]["value"]
    assert share == 1.0 if cell in FROZEN else 0 < share <= 1.0
    # the generator says what it holds its own lock for, beside the window's other diagnostics
    held = next(l for l in lines if "generator_compare_ms" in l)
    assert held["generator_compare_ms"]["count"] >= len([1 for l in lines if l.get("kind") == "get"])
    assert 0 < held["generator_compare_ms"]["mean"] <= held["generator_compare_ms"]["max"]
    assert held["generator_turnaround_ms"]["mean"] > 0 and held["gets_per_s"] > 0
    assert held["compare_share_of_one_lock"] == pytest.approx(
        held["gets_per_s"] * held["generator_compare_ms"]["mean"] / 1e3)
    assert 0 < m["get_blob_read_ms"]["value"] < 1000
    # no shard read twice (az1 read 13 / 12 = 1.083); a 6 s window on the CPU holds few
    # enough GETs that those in flight at its edges move the ratio by a per cent or two
    assert m["read_amp"]["value"] < 1.04


@pytest.mark.parametrize("name", ["get_blob_read_ms", "get_one_round_share"])
def test_entries_list_the_get_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"] if e["name"] == name)
    spec = layer(name)
    assert entry == {k: spec[k] for k in entry}
    assert entry["workloads"] == GET_CELLS and entry["moves"] == "get_MBps" and entry["layer"] == "access"


def ctx(before, after):
    return {"snap0": {"counters": before}, "snap1": {"counters": after}}


def stage(name, seconds, count):
    return {'cfs_trace_stage_seconds_sum{stage="%s"}' % name: seconds,
            'cfs_trace_stage_seconds_count{stage="%s"}' % name: count}


def plans(direct, one_round, two_round):
    return {'cfs_access_read_plan_total{plan="%s"}' % p: v
            for p, v in (("direct", direct), ("one_round", one_round), ("two_round", two_round))}


@pytest.mark.parametrize("after,blob_read_ms,share", [
    # two rounds a blob: 100 blobs, 0.81 s in access.read + 0.73 s in access.gather
    ({**stage("access.read", 0.81, 100), **stage("access.gather", 0.73, 100)}, 15.4, None),
    # one round a blob and the counter
    ({**stage("access.gather", 0.9, 100), **plans(0, 100, 0)}, 9.0, 1.0),
    # a healthy blob in ten beside them
    ({**stage("access.read", 0.05, 10), **stage("access.gather", 0.9, 90), **plans(10, 88, 2)}, 950 / 90, 0.88),
    # no degraded blob in the window: nothing to divide by
    ({**stage("access.read", 0.5, 100), **plans(100, 0, 0)}, None, 0.0),
])
def test_reducers_on_counters(after, blob_read_ms, share):
    zero = {k: 0.0 for k in after}
    for name, want in (("get_blob_read_ms", blob_read_ms), ("get_one_round_share", share)):
        spec = layer(name)
        got = load_reducer(spec["reducer"])(ctx(zero, after), spec["params"])
        assert got == pytest.approx(want) if want is not None else got is None, name
