"""Every metric's data file says what BENCHMARK.json's entry says, key for key
(`workloads` included: run.py reads BENCHMARK.json's, a reader reads the file
beside the reducer, and the two had drifted apart for 41 metrics), every file
is some entry's, and every reducer a file names exists."""
import json
import os

import pytest

from test_control import ROOT

BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    INDEX = json.load(f)
ENTRIES = [(folder, e) for group, folder in (("end_to_end", "endtoend"), ("per_layer", "layers"))
           for e in INDEX[group]]


@pytest.mark.parametrize("folder,entry", ENTRIES, ids=[e["name"] for _, e in ENTRIES])
def test_metric_file_is_the_benchmarks_entry(folder, entry):
    with open(os.path.join(BENCH, folder, entry["name"] + ".json")) as f:
        spec = json.load(f)
    # an end-to-end file carries neither bound nor cells: those are BENCHMARK.json's alone
    held = [k for k in entry if folder == "layers" or k not in ("bound", "workloads")]
    assert {k: spec.get(k) for k in held} == {k: entry[k] for k in held}
    assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
    cells = {w["name"] for w in INDEX["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    if folder == "layers":
        assert spec["what"] and "max_wait hold included" not in spec["what"]
        moved = next(m for m in INDEX["end_to_end"] if m["name"] == entry["moves"])
        assert set(entry["workloads"]) <= set(moved.get("workloads", cells))


def test_every_file_is_some_entrys():
    for group, folder in (("end_to_end", "endtoend"), ("per_layer", "layers")):
        files = {f[:-5] for f in os.listdir(os.path.join(BENCH, folder)) if f.endswith(".json")}
        assert files == {e["name"] for e in INDEX[group]}, folder
