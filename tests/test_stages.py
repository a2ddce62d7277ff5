"""trace.stage from gateway to blobnode (ISSUE 25): one pair of clock reads
lands on the profiler's clock, in cfs_trace_stage_seconds and on the request
Span. One PUT + degraded GET through Access with the CPU codec is recorded
once per arm (profiler session on / off); the cases read that recording."""

import glob
import subprocess
import sys
import time

import numpy as np
import pytest

from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.blobstore.cluster import MiniCluster
from chubaofs_tpu.utils import exporter

BLOB = 64 * 1024

# what an in-process PUT + degraded GET reaches: no gateway, no background tick
LIVE = ["access.put", "access.get", "access.prepare", "access.alloc",
        "access.encode_wait", "access.decode_wait", "access.write_stripe",
        "access.read", "access.gather",
        "codec.drain", "codec.stack", "codec.expand", "codec.concat", "codec.deliver",
        "hostbatch.group", "hostbatch.launch", "hostbatch.fetch"]
MARKS = ["access.sem_wait", "blobnode.put_shard", "blobnode.get_shard",
         "chunk.lock_wait", "chunk.write", "chunk.meta",
         "chunk.verify"]  # profiler's clock only
# counters only: no thread is inside (a wait), or the time is a sum of
# moments (a blob's pieces written into the GET's body as they arrive)
OBSERVED = ["access.pool_wait", "codec.queue_wait", "access.assemble"]
DISPATCHER = ("codec.drain", "codec.stack", "codec.expand", "codec.concat", "codec.deliver",
              "hostbatch.group", "hostbatch.launch", "hostbatch.fetch")


def scrape() -> dict[str, float]:
    """/metrics as the daemon renders it: {'name{labels}': value}."""
    out = {}
    for line in exporter.render_all().splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


def stage_counts() -> dict[str, float]:
    return {k.split('"')[1]: v for k, v in scrape().items()
            if k.startswith("cfs_trace_stage_seconds_count{")}


def put_and_degraded_get(root: str):
    """-> (client trace id, finished span records). 200 KiB in 64 KiB blobs
    is four EC6P3 stripes through the pipelined PUT; with node 1 gone every
    stripe's GET decodes."""
    records = []
    prev = trace.finish_hook()

    def hook(span):
        records.append(span.to_record())
        if prev is not None:
            prev(span)

    c = MiniCluster(root, n_nodes=9, disks_per_node=2)
    c.access.max_blob_size = BLOB
    data = np.random.default_rng(25).integers(0, 256, 200 * 1024, dtype=np.uint8).tobytes()
    trace.set_finish_hook(hook)
    try:
        with trace.Span("client.put") as client:
            loc = c.access.put(data)
        c.nodes.pop(1).close()
        with trace.Span("client.get"):
            assert c.access.get(loc) == data
    finally:
        trace.set_finish_hook(prev)
        c.close()
    return client.trace_id, records


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The run under a jax.profiler session: host-plane cfs: events as
    (stage, start_ns, end_ns, line index, req)."""
    import jax.profiler as prof

    root = tmp_path_factory.mktemp("traced")
    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    prof.start_trace(str(root / "trace"), profiler_options=opts)
    try:
        trace_id, records = put_and_degraded_get(str(root / "cluster"))
    finally:
        prof.stop_trace()
    path = sorted(glob.glob(str(root / "trace" / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = []
    for plane in prof.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("cfs:"):
                    events.append((e.name[4:], e.start_ns, e.start_ns + e.duration_ns,
                                   i, dict(e.stats).get("req")))
    return {"events": events, "trace_id": trace_id, "records": records}


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    """The same run with no profiler session: counter deltas and records."""
    before = stage_counts()
    trace_id, records = put_and_degraded_get(str(tmp_path_factory.mktemp("untraced")))
    after = stage_counts()
    return {"delta": {k: v - before.get(k, 0.0) for k, v in after.items()},
            "trace_id": trace_id, "records": records}


def test_the_name_sets_are_the_programs():
    assert set(LIVE + OBSERVED) <= trace.STAGES and set(MARKS) == trace.MARKS
    assert not trace.STAGES & trace.MARKS


@pytest.mark.parametrize("name", LIVE + MARKS)
def test_live_stage_is_on_the_host_plane(traced, name):
    assert any(e[0] == name for e in traced["events"])


@pytest.mark.parametrize("name", OBSERVED)
def test_observed_stage_has_no_annotation(traced, name):
    assert not any(e[0] == name for e in traced["events"])


def test_dispatcher_stages_nest_in_time(traced):
    """The dispatcher's stages sit on one thread and never partly overlap;
    every batch reads stack < group < launch < fetch < deliver."""
    evs = sorted((e for e in traced["events"] if e[0] in DISPATCHER), key=lambda e: e[1])
    assert len({e[3] for e in evs}) == 1, "one dispatcher thread"
    for a, b in zip(evs, evs[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    # expand comes with matmul (decode) batches only, concat with encode ones
    order = [e[0] for e in evs if e[0] not in ("codec.drain", "codec.expand", "codec.concat")]
    batch = ["codec.stack", "hostbatch.group", "hostbatch.launch", "hostbatch.fetch",
             "codec.deliver"]
    assert len(order) >= 5 and order == batch * (len(order) // 5)


def test_storage_stages_nest_inside_put_shard(traced):
    puts = [e for e in traced["events"] if e[0] == "blobnode.put_shard"]
    for name in ("chunk.lock_wait", "chunk.write", "chunk.meta"):
        for e in (e for e in traced["events"] if e[0] == name):
            assert any(p[3] == e[3] and p[1] <= e[1] and e[2] <= p[2] for p in puts), name


def test_verify_nests_inside_get_shard(traced):
    gets = [e for e in traced["events"] if e[0] == "blobnode.get_shard"]
    for e in (e for e in traced["events"] if e[0] == "chunk.verify"):
        assert any(g[3] == e[3] and g[1] <= e[1] and e[2] <= g[2] for g in gets)


def test_request_id_joins_annotation_and_rider_record(traced):
    """access.put's annotation carries the client's trace id, and the span of
    that trace got the codec.queue_wait stage its encode jobs rode in with."""
    reqs = {e[4] for e in traced["events"] if e[0] == "access.put"}
    assert reqs == {traced["trace_id"]}
    rider = [r for r in traced["records"]
             if r["trace_id"] == traced["trace_id"] and r["op"] == "access.put"]
    assert len(rider) == 1
    assert "codec.queue_wait" in {s[0] for s in rider[0]["stages"]}
    # write workers carry no span: their stages join by time, not by req
    assert {e[4] for e in traced["events"] if e[0] == "chunk.write"} == {None}


@pytest.mark.parametrize("name", LIVE + OBSERVED)
def test_stage_counter_grows_without_a_session(untraced, name):
    assert untraced["delta"].get(name, 0) >= 1


def test_marks_are_not_counted(untraced):
    assert not set(untraced["delta"]) & set(MARKS)


def test_untraced_span_record_is_what_cfs_trace_expects(untraced):
    from chubaofs_tpu.tools import cfstrace

    recs = [r for r in untraced["records"] if r["trace_id"] == untraced["trace_id"]]
    put = next(r for r in recs if r["op"] == "access.put")
    names = {s[0] for s in put["stages"]}
    assert names >= {"access.prepare", "access.alloc", "access.encode_wait",
                     "access.write_stripe", "codec.queue_wait", "codec.stack",
                     "codec.matmul"}
    # per-shard stages stay off the record (write workers carry no span)
    assert not names & {"chunk.write", "access.sem_wait", "blobnode.put_shard"}
    client = next(r for r in recs if r["op"] == "client.put")
    assert "access.put" in {s[0] for s in client["stages"]}
    assert {e.split(":")[0] for e in put["track"].split(";")} >= \
        {"proxy", "codec", "blobnode", "access"}
    rep = cfstrace.critical_path(recs, root_op="access.put")
    assert rep["coverage"] > 0.5 and rep["stages"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Counter deltas of one PUT + GET over HTTP (the evloop core) and one
    background tick: the stages an in-process call never reaches."""
    from chubaofs_tpu.blobstore.gateway import AccessClient, AccessGateway

    c = MiniCluster(str(tmp_path_factory.mktemp("served")), n_nodes=9, disks_per_node=2)
    gw = AccessGateway(c.access)
    try:
        before = stage_counts()
        client = AccessClient([gw.addr])
        data = bytes(range(256)) * 1024
        assert client.get(client.put(data)) == data
        c.run_background_once()
        # the server observes a reply's gateway.send after the client has
        # read it: on a loaded host give its thread a moment to get there
        deadline = time.monotonic() + 5.0
        while True:
            after = stage_counts()
            if after.get("gateway.send", 0) - before.get("gateway.send", 0) >= 2 \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.01)
    finally:
        gw.stop()
        c.close()
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


@pytest.mark.parametrize("name,least", [
    ("gateway.recv", 2), ("gateway.queue", 2), ("gateway.handle", 2),
    ("gateway.send", 2), ("access.assemble", 1), ("scheduler.scrub", 1), ("scheduler.inspect", 1)])
def test_gateway_and_background_stages_count(served, name, least):
    assert served.get(name, 0) >= least


def test_undeclared_stage_is_refused():
    with pytest.raises(ValueError):
        with trace.stage("made.up"):
            pass


def test_trace_module_runs_a_stage_without_jax():
    code = ("import sys\n"
            "from chubaofs_tpu.blobstore import trace\n"
            "with trace.stage('access.alloc'):\n    pass\n"
            "with trace.mark('chunk.write'):\n    pass\n"
            "trace.observe_stage('codec.queue_wait', 0.0, 0.001)\n"
            "assert 'jax' not in sys.modules, sorted(m for m in sys.modules if 'jax' in m)\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_dispatcher_stages_sum_to_dispatch_seconds():
    """codec.stack + codec.expand + hostbatch.* + codec.concat + codec.deliver
    is the dispatcher's batch time: within 5% of cfs_codec_dispatch_seconds
    over a hundred batches (deliver, the bookkeeping and the futures'
    wake-up, lies just outside that counter's interval)."""
    from chubaofs_tpu.codec.service import CodecService

    parts = ("codec.stack", "codec.expand", "hostbatch.group", "hostbatch.launch",
             "hostbatch.fetch", "codec.concat", "codec.deliver")

    def read():
        m = scrape()
        out = {p: m.get('cfs_trace_stage_seconds_sum{stage="%s"}' % p, 0.0) for p in parts}
        out["count"] = m.get("cfs_codec_dispatch_seconds_count", 0.0)
        out["whole"] = m.get("cfs_codec_dispatch_seconds_sum", 0.0)
        return out

    svc = CodecService(max_wait_ms=0.0)
    data = np.random.default_rng(4).integers(0, 256, (12, 256 * 1024), dtype=np.uint8)
    try:
        svc.encode(12, 4, data).result()  # compile outside the count
        a = read()
        for _ in range(100):
            svc.encode(12, 4, data).result()
        b = read()
    finally:
        svc.close()
    assert b["count"] - a["count"] == 100
    whole = b["whole"] - a["whole"]
    split = sum(b[p] - a[p] for p in parts)
    assert abs(split - whole) <= 0.05 * whole, (split, whole)


# -- a batch of one job is launched where it lies (ISSUE 37) --------------------


def _one_and_two(submit):
    """The result of ``submit(svc)`` alone in its batch, and twice in a batch of two."""
    from chubaofs_tpu.codec.service import CodecService

    out = []
    for count in (1, 2):
        svc = CodecService(max_batch=count, max_wait_ms=5000.0)
        try:
            before = svc.stats_snapshot()
            futures = [submit(svc) for _ in range(count)]
            out.append([np.array(f.result(60)) for f in futures])
            after = svc.stats_snapshot()
        finally:
            svc.close()
        assert (after["batches"] - before["batches"], after["jobs"] - before["jobs"]) == (1, count)
    return out


def _jobs():
    from chubaofs_tpu.codec.codemode import get_tactic

    rng = np.random.default_rng(37)
    exact = rng.integers(0, 256, (12, 16 * 1024), dtype=np.uint8)  # the bucket itself
    short = rng.integers(0, 256, (12, 5000), dtype=np.uint8)  # padded by the submitter
    lrc = rng.integers(0, 256, (6, 16 * 1024), dtype=np.uint8)
    present = [2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
    return {
        "encode": lambda svc: svc.encode(12, 4, exact),
        "encode-padded": lambda svc: svc.encode(12, 4, short),
        "decode_rows": lambda svc: svc.decode_rows(12, 4, present, exact, [0, 1]),
        "decode_rows-padded": lambda svc: svc.decode_rows(12, 4, present, short, [0, 1]),
        "encode_tactic-lrc": lambda svc: svc.encode_tactic(get_tactic("EC6P3L3"), lrc),
        "reconstruct": lambda svc: svc.reconstruct(12, 4, np.concatenate([exact, exact[:4]]), [0, 13]),
    }


@pytest.mark.parametrize("name", sorted(_jobs()))
def test_one_job_batch_gives_the_bytes_of_a_batch_of_two(name):
    (alone,), (first, second) = _one_and_two(_jobs()[name])
    assert alone.dtype == np.uint8 and alone.shape == first.shape
    assert np.array_equal(alone, first) and np.array_equal(alone, second)


@pytest.mark.parametrize("kind", ["encode", "decode_rows"])
def test_input_may_be_overwritten_once_the_future_is_done(kind):
    """The one-job view reads the caller's rows during the launch, inside the
    call: a result already delivered does not change when they do."""
    from chubaofs_tpu.codec.service import CodecService
    from chubaofs_tpu.ops import gf256, rs

    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (12, 16 * 1024), dtype=np.uint8)
    kernel = rs.get_kernel(12, 4)
    present = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    svc = CodecService(max_batch=1)
    try:
        for _ in range(3):
            keep = data.copy()
            if kind == "encode":
                got = svc.encode(12, 4, data).result(60)
                expect = np.concatenate([keep, gf256.gf_matmul(kernel.gen[12:], keep)])
            else:
                got = svc.decode_rows(12, 4, present, data, [0]).result(60)
                expect = gf256.gf_matmul(kernel.window_matrix(present, [0]), keep)
            data[:] = rng.integers(0, 256, data.shape, dtype=np.uint8)
            assert np.array_equal(got, expect)
    finally:
        svc.close()


def test_cancelled_one_job_batch_does_no_device_work(monkeypatch):
    from chubaofs_tpu.codec.service import CodecService
    from chubaofs_tpu.ops import rs

    import threading

    sound, calls, entered, release = rs.gf_matmul_hostbatch, [], threading.Event(), threading.Event()

    def counting(mat_bits, shards):
        calls.append(shards.shape)
        entered.set()
        release.wait(30)
        return sound(mat_bits, shards)

    monkeypatch.setattr(rs, "gf_matmul_hostbatch", counting)
    data = np.random.default_rng(6).integers(0, 256, (12, 16 * 1024), dtype=np.uint8)
    svc = CodecService(max_batch=1)
    try:
        first = svc.encode(12, 4, data)
        assert entered.wait(30)  # the dispatcher is inside the first batch
        dropped = svc.decode_rows(12, 4, list(range(1, 13)), data, [0])
        assert dropped.cancel()
        last = svc.encode(12, 4, data)
        release.set()
        assert first.result(60).shape == last.result(60).shape == (16, 16 * 1024)
    finally:
        release.set()
        svc.close()
    assert dropped.cancelled() and calls == [(1, 12, 16 * 1024)] * 2


@pytest.mark.parametrize("kind,expect", [
    ("encode", ["codec.stack", "hostbatch.group", "hostbatch.launch", "hostbatch.fetch",
                "codec.concat", "codec.deliver"]),
    # codec.concat is entered for every kind of job (an encode's parity goes into its slot there)
    ("decode_rows", ["codec.stack", "codec.expand", "hostbatch.group", "hostbatch.launch",
                     "hostbatch.fetch", "codec.concat", "codec.deliver"]),
])
def test_dispatcher_stage_list_and_order_are_the_same_on_a_hit(monkeypatch, kind, expect):
    """A miss (the first batch of a matrix) and a hit enter the same stages in
    the same order, once a batch each: the per-job metrics keep their meaning."""
    from chubaofs_tpu.codec.service import CodecService
    from chubaofs_tpu.utils.exporter import registry

    entered: list[str] = []
    sound = trace.stage

    def recording(name, *a, **kw):
        if name != "codec.drain":
            entered.append(name)
        return sound(name, *a, **kw)

    monkeypatch.setattr(trace, "stage", recording)
    data = np.random.default_rng(7).integers(0, 256, (12, 32 * 1024), dtype=np.uint8)
    # a pattern no other test of this process uses: the first batch is a miss
    present = [0, 1, 2, 4, 5, 6, 7, 8, 10, 11, 14, 15]
    reg = registry("codec")
    hit, miss = (reg.counter("plan_total", {"result": r}) for r in ("hit", "miss"))
    svc = CodecService(max_batch=1)
    try:
        seen = []
        for _ in range(3):
            del entered[:]
            before = (hit.value, miss.value)
            if kind == "encode":
                svc.encode(12, 4, data).result(60)
            else:
                svc.decode_rows(12, 4, present, data, [3, 9]).result(60)
            seen.append((list(entered), hit.value - before[0], miss.value - before[1]))
    finally:
        svc.close()
    assert [s[0] for s in seen] == [expect] * 3
    if kind == "decode_rows":
        assert [s[1:] for s in seen] == [(0, 1), (1, 0), (1, 0)]
    assert seen[-1][1:] == (1, 0)


# -- the drain is work-conserving: no timer with a job in hand (ISSUE 40) --------


def _closes() -> dict[str, float]:
    return {c: exporter.registry("codec").counter("batch_close_total", {"close": c}).value
            for c in ("empty", "full", "held")}


def _drain_reading() -> dict[str, float]:
    m = scrape()
    out = {k: m.get('cfs_trace_stage_seconds_%s{stage="codec.drain"}' % k, 0.0)
           for k in ("sum", "count")}
    out.update(_closes())
    return out


def _grew(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _until(cond, timeout: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.001)
    return cond()


def _parked(svc, monkeypatch):
    """Park the dispatcher inside its next batch: -> (sizes, entered, release).
    ``sizes`` takes the job count of every group launched from then on."""
    import threading

    sound, sizes = svc._run_group, []
    entered, release = threading.Event(), threading.Event()

    def gated(sig, jobs):
        sizes.append(len(jobs))
        entered.set()
        release.wait(30)
        return sound(sig, jobs)

    monkeypatch.setattr(svc, "_run_group", gated)
    return sizes, entered, release


@pytest.mark.parametrize("name", ["encode", "decode_rows", "encode_tactic-lrc"])
def test_a_lone_job_is_launched_without_a_wait(name):
    """A default service with nothing queued behind the job closes the batch
    on the spot: codec.drain is a sweep of an empty queue, not a 2 ms hold."""
    from chubaofs_tpu.codec.service import CodecService

    submit, n = _jobs()[name], 40
    svc = CodecService()
    try:
        submit(svc).result(60)  # compile outside the reading
        before = _drain_reading()
        for _ in range(n):
            submit(svc).result(60)
        grew = _grew(before, _drain_reading())
    finally:
        svc.close()
    assert (grew["count"], grew["empty"], grew["full"], grew["held"]) == (n, n, 0, 0)
    assert grew["sum"] / n < 0.5e-3, grew


@pytest.mark.parametrize("k", [2, 5, 32, 40])
def test_what_queued_behind_a_running_batch_is_one_batch(monkeypatch, k):
    """Batches form by themselves: everything that arrived while the
    dispatcher was busy is taken by the next sweep, up to max_batch."""
    from chubaofs_tpu.codec.service import CodecService

    submit = _jobs()["encode"]
    svc = CodecService()
    sizes, entered, release = _parked(svc, monkeypatch)
    try:
        before = _closes()
        first = submit(svc)
        assert entered.wait(30)  # the dispatcher is inside the first batch
        queued = [submit(svc) for _ in range(k)]
        release.set()
        for f in [first] + queued:
            assert f.result(60).shape == (16, 16 * 1024)
        grew = _grew(before, _closes())
    finally:
        release.set()
        svc.close()
    want = [1, min(k, svc.max_batch)] + ([k - svc.max_batch] if k > svc.max_batch else [])
    assert sizes == want
    assert grew == {"empty": len(want) - (k >= svc.max_batch),
                    "full": int(k >= svc.max_batch), "held": 0}


@pytest.mark.parametrize("b", [1, 2, 7, 32])
def test_a_hold_set_on_a_live_service_drains_an_exact_count(b):
    """The instrument benchmark/deploy.py's warm-up plays: max_wait and
    max_batch set on a running default service make b jobs submitted one by
    one ride ONE batch; restoring both restores the unheld drain."""
    from chubaofs_tpu.codec.service import CodecService

    submit = _jobs()["encode"]
    svc = CodecService()
    try:
        submit(svc).result(60)  # live: the dispatcher waits for its next job
        keep = (svc.max_batch, svc.max_wait)
        assert keep == (32, 0.0)
        svc.max_wait = 5.0
        try:
            svc.max_batch = b
            stats, closes = svc.stats_snapshot(), _closes()
            for f in [submit(svc) for _ in range(b)]:
                f.result(60)
            after = svc.stats_snapshot()
            grew = _grew(closes, _closes())
        finally:
            svc.max_batch, svc.max_wait = keep
        assert (after["batches"] - stats["batches"], after["jobs"] - stats["jobs"]) == (1, b)
        # the b-th job closes the batch, out of the sweep or out of the hold
        assert grew["empty"] == 0 and grew["full"] + grew["held"] == 1
        assert b > 1 or grew["full"] == 1
        before = _drain_reading()
        t0 = time.monotonic()
        submit(svc).result(60)
        assert time.monotonic() - t0 < 2.0
        grew = _grew(before, _drain_reading())
        assert (grew["count"], grew["empty"], grew["held"]) == (1, 1, 0)
    finally:
        svc.close()


@pytest.mark.parametrize("hold_ms", [0.0, 5000.0])
def test_the_sentinel_ends_a_batch_and_is_posted_again(monkeypatch, hold_ms):
    """close() behind queued jobs: the sweep (unheld: the dispatcher was busy
    while they queued) or the hold (it sat waiting for more) stops at the
    sentinel, the jobs in hand are served, the loop then meets it and ends."""
    import threading

    from chubaofs_tpu.codec.service import CodecService

    submit = _jobs()["encode"]
    svc = CodecService(max_wait_ms=hold_ms)
    sizes, entered, release = _parked(svc, monkeypatch)
    closer = threading.Thread(target=svc.close)
    try:
        before = _closes()
        futures = [submit(svc)]
        if hold_ms:
            assert _until(lambda: not svc._q.qsize())
            time.sleep(0.05)  # the first job is taken: the dispatcher sits in the hold
        else:
            assert entered.wait(30)  # inside the first batch; the rest queue up
        futures += [submit(svc), submit(svc)]
        t0 = time.monotonic()
        closer.start()
        if not hold_ms:
            assert _until(lambda: svc._q.qsize() == 3)  # two jobs, then the sentinel
        release.set()
        for f in futures:
            assert f.result(60).shape == (16, 16 * 1024)
        closer.join(10)
        svc._thread.join(10)
        assert not closer.is_alive() and not svc._thread.is_alive()
        assert time.monotonic() - t0 < 4.0  # a hold does not outlast the sentinel
    finally:
        release.set()
        closer.join(10)
    grew = _grew(before, _closes())
    if hold_ms:
        assert sizes == [3] and grew == {"empty": 0, "full": 0, "held": 1}
    else:
        assert sizes == [1, 2] and grew == {"empty": 2, "full": 0, "held": 0}
    with pytest.raises(RuntimeError):
        submit(svc)


@pytest.mark.parametrize("cancelled", [[0], [1], [0, 2], [0, 1, 2]])
def test_a_cancelled_job_in_a_swept_batch_is_skipped(monkeypatch, cancelled):
    from chubaofs_tpu.codec.service import CodecService

    submit = _jobs()["encode"]
    svc = CodecService()
    sizes, entered, release = _parked(svc, monkeypatch)
    try:
        before = _closes()
        first = submit(svc)
        assert entered.wait(30)
        queued = [submit(svc) for _ in range(3)]
        for i in cancelled:
            assert queued[i].cancel()
        release.set()
        assert first.result(60).shape == (16, 16 * 1024)
        for i, f in enumerate(queued):
            assert f.cancelled() if i in cancelled else f.result(60).shape == (16, 16 * 1024)
        assert _until(lambda: not svc._q.qsize())  # all three cancelled: wait for the sweep itself
        assert submit(svc).result(60).shape == (16, 16 * 1024)  # the service goes on
    finally:
        release.set()
        svc.close()
    # the swept batch launches its live jobs only; one that is all cancelled launches nothing
    assert sizes == [1] + [3 - len(cancelled)] * (len(cancelled) < 3) + [1]
    assert _grew(before, _closes()) == {"empty": 3, "full": 0, "held": 0}


# -- the per-layer metrics that read codec.drain ---------------------------------

DRAIN_LAYERS = [
    ("small_codec_drain_ms", ["az1.small-open"], "op_p50_ms"),
    ("get_codec_drain_ms", ["az1.get16m-nodedown", "az2.get16m-azdown", "az1.get16m-rebuild"],
     "get_MBps"),
    ("codec_drain_ms", ["az1.put16m", "az3.put16m", "az2.put16m"], "put_MBps"),
]


@pytest.mark.parametrize("name,cells,moves", DRAIN_LAYERS)
def test_drain_layer_is_the_benchmarks_entry_and_reads_a_live_service(name, cells, moves):
    import importlib.util
    import json
    import os

    from chubaofs_tpu.codec.service import CodecService

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "layers", name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    assert entry == {k: spec[k] for k in entry}
    assert (entry["workloads"], entry["moves"], entry["layer"]) == (cells, moves, "codec service")
    assert 76 <= bench["per_layer"].index(entry) < 76 + len(DRAIN_LAYERS), "appended to PR 38's 76"
    assert set(cells) <= set(next(m for m in bench["end_to_end"] if m["name"] == moves)["workloads"])
    assert spec["params"] == {"stages": ["codec.drain"]} and "codec.drain" in trace.STAGES
    modspec = importlib.util.spec_from_file_location(
        "reducer_" + spec["reducer"], os.path.join(bench_dir, "reducers", spec["reducer"] + ".py"))
    mod = importlib.util.module_from_spec(modspec)
    sys.path.insert(0, bench_dir)
    try:
        modspec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench_dir)

    def reduce(before, after):
        return mod.reduce({"snap0": {"counters": before}, "snap1": {"counters": after}},
                          spec["params"])

    total, count = ('cfs_trace_stage_seconds_%s{stage="codec.drain"}' % k for k in ("sum", "count"))
    assert reduce({total: 1.0, count: 100.0}, {total: 1.5, count: 350.0}) == 2.0
    assert reduce({total: 1.0, count: 100.0}, {total: 1.0, count: 100.0}) is None  # no batch: nothing
    submit = _jobs()["encode"]
    svc = CodecService()
    try:
        submit(svc).result(60)
        before = scrape()
        for _ in range(20):
            submit(svc).result(60)
        read = reduce(before, scrape())
    finally:
        svc.close()
    assert 0.0 < read < 0.5, read
