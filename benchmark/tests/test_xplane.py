"""The trace reducers on one small trace recorded on a TPU v5e (PR 24): three
EC12P4 encode batches (1, 4 and 7 jobs) inside bench:window, each inside its
own bench:batchN annotation."""
import os

import pytest

import kernelmodel
import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "three_batches.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(TRACE)


def test_planes_and_annotations(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert len(trace["devices"]["/device:TPU:0"]) == 6
    assert [n for n, _, _ in trace["annotations"]] == [
        "bench:window", "bench:batch1", "bench:batch4", "bench:batch7"]


def test_kernel_name_match_and_bytes(trace):
    calls = [kernelmodel.parse(n) for n, _, _ in trace["devices"]["/device:TPU:0"]]
    kernels = [c for c in calls if c]
    assert [(c["b"], c["r"], c["n"], c["k"]) for c in kernels] == [
        (1, 4, 12, 524288), (1, 16, 48, 524288), (7, 4, 12, 524288)]
    # 7 stripes of 12 data + 4 parity shards of 512 KiB, plus the 32 x 96 matrix
    assert kernels[2]["bytes"] == 7 * 16 * 524288 + 32 * 96
    assert kernelmodel.parse("%copy = u8[7,12,524288]{2,1,0} copy(u8[7,12,524288]{2,0,1} %data.1)") is None


def test_busy_union_and_idle_gaps(trace):
    s = xplane.device_summary(trace, "bench:window")
    assert s["device"]["window_s"] == pytest.approx(0.223099, abs=1e-6)
    assert s["device"]["busy_s"] == pytest.approx(0.000748738, rel=1e-6)
    names = [n for n, _ in s["breakdown"]["idle_gaps"]]
    # the longest gaps lie between the batches (nothing of the benchmark's runs
    # there); the gap at the window's start lies inside the first batch's span
    assert names[:3] == ["host:_unattributed"] * 3 and "host:bench:batch1" in names
    total_gap = sum(g for _, g in s["breakdown"]["idle_gaps"])
    assert total_gap + s["device"]["busy_s"] == pytest.approx(s["device"]["window_s"], rel=1e-9)
    top = s["breakdown"]["device_ops"][0]
    assert top[0].startswith("fused_core.1_u8_7_4_524288") and top[1] == pytest.approx(0.0004373, rel=1e-3)


def test_union_merges_overlaps_and_gaps_clip_to_the_window():
    evs = [("a", 1.0, 2.0), ("b", 1.5, 2.5), ("c", 4.0, 5.0), ("d", 9.5, 12.0)]
    assert xplane.union(evs) == [(1.0, 2.5), (4.0, 5.0), (9.5, 12.0)]
    assert xplane.busy_seconds(xplane.clip(evs, 0, 10)) == pytest.approx(3.0)
    assert xplane.gaps(evs, 0, 10) == [(5.0, 9.5), (2.5, 4.0), (0, 1.0)]


def test_roofline_share_of_the_recorded_calls(trace):
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    shares = []
    for n, s, e in trace["devices"]["/device:TPU:0"]:
        c = kernelmodel.parse(n)
        if c:
            shares.append(kernelmodel.least_seconds(c, peaks) / (e - s))
    assert all(0.1 < x < 0.35 for x in shares), shares
