"""The daemon's stages on the profiler's clock: hostspans' projection on a
hand-made event list, the idle_named_pct reducer with that list laid over the
device events of the recorded v5e trace (three_batches.xplane.pb), and
hostspans.load on a trace this test records itself on the CPU backend."""
import glob
import importlib.util
import os
import threading

import pytest

import hostspans
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "three_batches.xplane.pb")

# (stage, start_s, end_s, line, req): one request on line 1 whose encode waits
# on line 2, the dispatcher on line 3, three write workers on lines 4-6, the
# background tick (and a shard read under it) on line 7
EVENTS = [
    ("gateway.handle", 0.0, 10.0, 1, "r1"), ("access.put", 0.1, 9.9, 1, "r1"),
    ("access.alloc", 0.2, 0.5, 1, "r1"), ("access.encode_wait", 1.0, 3.0, 2, "r1"),
    ("codec.stack", 1.5, 2.0, 3, None), ("hostbatch.fetch", 2.0, 2.5, 3, None),
    ("chunk.write", 4.0, 6.0, 4, None), ("chunk.write", 4.5, 6.5, 5, None),
    ("chunk.crc", 4.0, 5.0, 6, None),
    ("scheduler.tick", 0.0, 20.0, 7, None), ("blobnode.get_shard", 3.0, 3.5, 7, None)]


def test_projection_names_every_moment_once():
    got = hostspans.project(EVENTS, 0.0, 12.0)
    assert got == [
        ("cfs:wait/unnamed", 0.0, 0.2), ("cfs:wait/access.alloc", 0.2, 0.5),
        ("cfs:wait/unnamed", 0.5, 1.0), ("cfs:wait/access.encode_wait", 1.0, 1.5),
        ("cfs:codec.stack", 1.5, 2.0), ("cfs:hostbatch.fetch", 2.0, 2.5),
        ("cfs:wait/access.encode_wait", 2.5, 3.0),
        # the tick's own shard read (3.0-3.5) is not the request path's
        ("cfs:wait/unnamed", 3.0, 4.0), ("cfs:wait/chunk.write", 4.0, 6.5),
        ("cfs:wait/unnamed", 6.5, 10.0), ("cfs:idle/empty", 10.0, 12.0)]
    assert got[0][1] == 0.0 and got[-1][2] == 12.0
    assert all(a[2] == b[1] for a, b in zip(got, got[1:]))


def test_innermost_and_thread_seconds():
    segs = hostspans.innermost(EVENTS)
    line1 = sorted(s for s in segs if s[3] == 1)
    assert [(n, a, b) for n, a, b, _, _ in sorted(line1, key=lambda s: s[1])] == [
        ("gateway.handle", 0.0, 0.1), ("access.put", 0.1, 0.2), ("access.alloc", 0.2, 0.5),
        ("access.put", 0.5, 9.9), ("gateway.handle", 9.9, 10.0)]
    assert {s[4] for s in segs if s[3] == 7} == {"scheduler.tick"}
    secs = hostspans.thread_seconds(segs, 4.0, 6.0)
    assert secs == pytest.approx({"access.put": 2.0, "chunk.write": 3.5, "chunk.crc": 1.0,
                                  "scheduler.tick": 2.0})
    assert hostspans.overlap_seconds([(0, 1), (2, 5)], [(0.5, 3), (4, 9)]) == pytest.approx(2.5)


def load_reducer(name):
    path = os.path.join(os.path.dirname(HERE), "reducers", name + ".py")
    spec = importlib.util.spec_from_file_location("reducer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def test_idle_named_pct_over_the_recorded_device_events(monkeypatch):
    """Stages laid over the three recorded batches: the dispatcher inside each
    batch, chunk writes across the first pause, a request open and nothing
    below it across the second. After the merge every gap has a name."""
    trace = xplane.load(TRACE)
    lo, hi = xplane.window_of(trace, "bench:window")
    b1, b4, b7 = (xplane.window_of(trace, "bench:batch%d" % n) for n in (1, 4, 7))
    events = [("gateway.handle", lo, b7[1], 1, "r1")]
    for s, e in (b1, b4, b7):
        mid = (s + e) / 2
        events += [("codec.stack", s, mid, 3, None), ("hostbatch.fetch", mid, e, 3, None)]
    events += [("chunk.write", b1[1], b4[0], 4, None), ("chunk.meta", b1[1], (b1[1] + b4[0]) / 2, 5, None)]
    monkeypatch.setattr(hostspans, "trace_path", lambda: TRACE)
    monkeypatch.setattr(hostspans, "load", lambda path: events)
    said = []
    ctx = {"trace": trace, "snap0": {"counters": {}}, "snap1": {"counters": {}},
           "say": lambda **kw: said.append(kw)}
    value = load_reducer("trace_idle_named_pct")(ctx, {})
    device = trace["devices"]["/device:TPU:0"]
    idle = sorted(xplane.gaps(device, lo, hi))
    # a request open and no stage below it: before the first batch, and the second pause
    unnamed = hostspans.overlap_seconds(idle, [(lo, b1[0]), (b4[1], b7[0])])
    assert value == pytest.approx(100.0 * (1 - unnamed / sum(e - s for s, e in idle)))
    assert 50 < value < 90
    names = [n for n, _ in xplane.device_summary(trace, "bench:window")["breakdown"]["idle_gaps"]]
    assert "host:_unattributed" not in names
    assert names[:3] == ["host:cfs:wait/unnamed", "host:cfs:wait/chunk.write", "host:cfs:idle/empty"]
    gaps = next(kw for kw in said if "longest_idle_gaps" in kw)
    assert gaps["host_spans"] == len(events)
    assert gaps["longest_idle_gaps"][1]["thread_seconds"]["chunk.write"] == \
        pytest.approx(b4[0] - b1[1], rel=1e-3)
    # a second metric of this reducer in one cell must not add the labels twice
    n = len(trace["annotations"])
    load_reducer("trace_idle_named_pct")(ctx, {})
    assert len(trace["annotations"]) == n


def test_a_program_without_stages_reads_none(monkeypatch):
    trace = xplane.load(TRACE)
    monkeypatch.setattr(hostspans, "trace_path", lambda: TRACE)  # holds bench: spans only
    ctx = {"trace": trace, "snap0": {"counters": {}}, "snap1": {"counters": {}}, "say": print}
    assert load_reducer("trace_idle_named_pct")(ctx, {}) is None
    assert load_reducer("trace_idle_named_pct")(dict(ctx, trace=None), {}) is None
    names = [n for n, _ in xplane.device_summary(trace, "bench:window")["breakdown"]["idle_gaps"]]
    assert names[:3] == ["host:_unattributed"] * 3  # as before this reducer existed


def test_counter_reducers_on_a_program_with_and_without_the_counters():
    a = {"t": 0.0, "counters": {'cfs_trace_stage_seconds_sum{stage="gateway.recv"}': 1.0,
                                'cfs_trace_stage_seconds_count{stage="gateway.recv"}': 10.0,
                                "cfs_blobnode_shard_put_count": 100.0,
                                'cfs_blobnode_shard_put_bucket{le="0.1"}': 100.0}}
    b = {"t": 10.0, "counters": {'cfs_trace_stage_seconds_sum{stage="gateway.recv"}': 1.5,
                                 'cfs_trace_stage_seconds_count{stage="gateway.recv"}': 60.0,
                                 "cfs_blobnode_shard_put_count": 300.0,
                                 'cfs_blobnode_shard_put_bucket{le="0.1"}': 290.0,
                                 "cfs_codec_jobs_total": 25.0}}
    ctx = {"snap0": a, "snap1": b}
    assert load_reducer("stage_ms")(ctx, {"stages": ["gateway.recv"]}) == pytest.approx(10.0)
    per_job = {"num": ['cfs_trace_stage_seconds_sum{stage="gateway.recv"}'], "den": ["cfs_codec_jobs_total"]}
    assert load_reducer("stage_ms")(ctx, per_job) == pytest.approx(20.0)
    over = {"summary": "cfs_blobnode_shard_put", "le": "0.1"}
    assert load_reducer("hist_over_pct")(ctx, over) == pytest.approx(5.0)
    assert load_reducer("stage_busy_pct")(ctx, {"stages": ["gateway.recv"]}) == pytest.approx(5.0)
    # the parent counts codec jobs but renders no stage: nothing to read, not 0
    parent = {"snap0": {"t": 0.0, "counters": {}}, "snap1": {"t": 10.0, "counters": {"cfs_codec_jobs_total": 25.0}}}
    assert load_reducer("stage_ms")(parent, {"stages": ["gateway.recv"]}) is None
    assert load_reducer("stage_ms")(parent, per_job) is None
    assert load_reducer("stage_busy_pct")(parent, {"stages": ["gateway.recv"]}) is None
    assert load_reducer("hist_over_pct")(parent, over) is None


def test_load_reads_stages_from_a_trace_recorded_here(tmp_path):
    """trace.stage under a jax.profiler session on the CPU backend: the
    events come back with their nesting, their thread and the request's id."""
    import jax.profiler as prof

    from chubaofs_tpu.blobstore import trace

    def worker():
        with trace.mark("chunk.write"):
            pass

    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    prof.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.Span("client") as span, trace.stage("access.put"):
            with trace.stage("access.alloc"):
                t = threading.Thread(target=worker)
                t.start()
                t.join()
    finally:
        prof.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = {e[0]: e for e in hostspans.load(path)}
    assert set(events) == {"access.put", "access.alloc", "chunk.write"}
    put, alloc, write = events["access.put"], events["access.alloc"], events["chunk.write"]
    assert put[4] == alloc[4] == span.trace_id and write[4] is None
    assert put[3] == alloc[3] != write[3]
    assert put[1] <= alloc[1] <= write[1] and write[2] <= alloc[2] <= put[2]
    roots = {s[0]: s[4] for s in hostspans.innermost(list(events.values()))}
    assert roots == {"access.put": "access.put", "access.alloc": "access.put",
                     "chunk.write": "chunk.write"}
