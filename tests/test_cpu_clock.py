"""CPU beside wall (ISSUE 38): cfs_proc_cpu_seconds{role} is every Python
thread's kernel CPU clock summed by thread role where /metrics is rendered,
cfs_trace_stage_cpu_seconds{stage} a stage's thread CPU under a profiler
session and at no other time, and the eighteen benchmark/layers files that
read them are BENCHMARK.json's entries and read nothing from a program
without the series."""

import importlib.util
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from chubaofs_tpu.blobstore import trace
from chubaofs_tpu.tools.cfsstat import parse_metrics
from chubaofs_tpu.utils import exporter, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
PYTHON_ROLES = ("loop", "request", "io", "codec", "tick", "repair", "reclaim", "other")
ROLE_PCT = PYTHON_ROLES[:5]  # the roles the PUT and GET cells report as % of one core

pytestmark = pytest.mark.skipif(not hasattr(time, "pthread_getcpuclockid"),
                                reason="no per-thread CPU clock on this platform")


def scrape() -> dict[str, float]:
    """/metrics as the daemon renders it: {'name{labels}': value}."""
    return parse_metrics(exporter.render_all())


def role_series(role: str) -> str:
    return 'cfs_proc_cpu_seconds{role="%s"}' % role


def cpu_by_role(snap=None) -> dict[str, float]:
    snap = scrape() if snap is None else snap
    return {r: snap[role_series(r)] for r in trace.ROLES}


def spin(seconds: float) -> float:
    """Burn this thread's CPU for ``seconds`` of it; the CPU seconds it took."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass
    return time.thread_time() - t0


class Worker:
    """A named thread that spins for a given CPU time each time it is told to,
    and otherwise waits: alive (so scraped) between two bursts."""

    def __init__(self, name: str):
        self.ran = 0.0
        self._go, self._done, self._burst = threading.Event(), threading.Event(), 0.0
        self.thread = threading.Thread(target=self._run, name=name, daemon=True)
        self.thread.start()

    def _run(self):
        while True:
            self._go.wait()
            self._go.clear()
            if self._burst is None:
                return
            self.ran += spin(self._burst)
            self._done.set()

    def burn(self, seconds: float):
        self._burst = seconds
        self._done.clear()
        self._go.set()
        assert self._done.wait(60)

    def end(self):
        self._burst = None
        self._go.set()
        self.thread.join(10)
        assert not self.thread.is_alive()


# -- the role set and the one mapping ---------------------------------------------


def test_role_set_is_the_rendered_label_set():
    mapped = {role for _, role in trace._ROLE_OF_PREFIX}
    assert mapped | {"other", "native"} == set(trace.ROLES) and len(set(trace.ROLES)) == 9
    rendered = {k.split('"')[1] for k in scrape() if k.startswith("cfs_proc_cpu_seconds{")}
    assert rendered == set(trace.ROLES)


@pytest.mark.parametrize("name,role", [
    ("evloop-http-access-0", "loop"), ("evloop-http-access-accept", "loop"),
    ("evw-http-access-13", "request"), ("access-pipe_7", "request"),
    ("access-read_15", "io"), ("access_3", "io"), ("access-probe_0", "io"),
    ("access-probe-io_2", "io"), ("codec-svc", "codec"), ("blobstore-bg", "tick"),
    ("repair-worker", "repair"), ("repair-stripe_1", "repair"), ("repair-io_9", "repair"),
    ("reclaim-worker", "reclaim"), ("reclaim-io_3", "reclaim"),
    ("MainThread", "other"), ("cfs-prof-cont", "other"), ("blobstore-reload", "other"),
    ("Thread-4 (serve)", "other"), ("access", "other"), ("", "other"), ("?", "other"),
])
def test_thread_name_maps_to_its_role(name, role):
    assert trace.thread_role(name) == role
    # the sampling profiler folds its buckets by the same mapping
    assert trace.thread_role(profiler.thread_bucket(name)) == role


def test_profile_totals_samples_by_the_counters_roles():
    prof = profiler.Profile(97.0)
    prof.add_sweep([("evw-http-access-N", ("a",)), ("access-pipe_N", ("b",)),
                    ("codec-svc", ("c",)), ("MainThread", ("d",)), ("?", ("e",))])
    assert prof.role_totals() == {"request": 2, "codec": 1, "other": 2}
    assert prof.to_dict()["roles"] == prof.role_totals()


def test_undeclared_role_is_refused(monkeypatch):
    """A mapping that names a role outside ROLES fails the scrape loudly: it
    cannot mint a ninth series."""
    monkeypatch.setattr(trace, "_ROLE_OF_PREFIX", (("made-up-", "made.up"),) + trace._ROLE_OF_PREFIX)
    w = Worker("made-up-0")
    try:
        with pytest.raises(KeyError):
            trace.collect_cpu()
    finally:
        w.end()
    monkeypatch.undo()
    assert {k.split('"')[1] for k in scrape() if k.startswith("cfs_proc_cpu_seconds{")} == set(trace.ROLES)


# -- every thread a served daemon starts has a role ------------------------------


@pytest.fixture(scope="module")
def daemon_threads(tmp_path_factory):
    """Names of the threads a served blobstore daemon started for a PUT, a
    degraded GET and the rebuild of a node declared broken."""
    from chubaofs_tpu import cmd
    from chubaofs_tpu.blobstore.gateway import AccessClient

    before = set(threading.enumerate())
    d = cmd.start_role({"role": "blobstore", "root": str(tmp_path_factory.mktemp("daemon")),
                        "listen": "127.0.0.1:0", "nodes": 9, "disksPerNode": 2, "azs": 1,
                        "jaxPlatform": "cpu"})
    names = set()
    try:
        client = AccessClient([d.addr])
        data = os.urandom(5 << 20)  # two EC12P4 blobs
        loc = client.put(data)
        cluster = d.runner.handles["cluster"]
        d.runner.call_with("cluster", lambda c: c.nodes.pop(1).close())
        assert client.get(loc) == data
        for disk in [k for k in cluster.cm.disks.values() if k.node_id == 1]:
            req = urllib.request.Request(
                "http://%s/admin/disk/set?disk_id=%d&status=broken" % (d.addr, disk.disk_id), method="POST")
            urllib.request.urlopen(req, timeout=30).read()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            names |= {t.name for t in set(threading.enumerate()) - before}
            if all(any(trace.thread_role(n) == r for n in names) for r in PYTHON_ROLES[:-1]):
                break
            time.sleep(0.05)
    finally:
        d.stop()
    return names


def test_no_daemon_thread_falls_to_other(daemon_threads):
    """A renamed pool fails here; it does not silently move to `other`."""
    assert {n for n in daemon_threads if trace.thread_role(n) == "other"} == set()


@pytest.mark.parametrize("role", PYTHON_ROLES[:-1])
def test_daemon_has_threads_of_role(daemon_threads, role):
    assert any(trace.thread_role(n) == role for n in daemon_threads), sorted(daemon_threads)


def test_minicluster_threads_have_roles(tmp_path):
    from chubaofs_tpu.blobstore.cluster import MiniCluster

    before = set(threading.enumerate())
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        data = bytes(range(256)) * 1024
        assert c.access.get(c.access.put(data)) == data
        c.run_background_once()
        names = {t.name for t in set(threading.enumerate()) - before}
    finally:
        c.close()
    assert names and {n for n in names if trace.thread_role(n) == "other"} == set()


# -- the counter: a partition of the process's CPU clock, monotone -----------------


def layer(name: str) -> dict:
    with open(os.path.join(BENCH, "layers", name + ".json")) as f:
        return json.load(f)


def reduce(name: str, snap0: dict, snap1: dict):
    spec = layer(name)
    path = os.path.join(BENCH, "reducers", spec["reducer"] + ".py")
    modspec = importlib.util.spec_from_file_location("reducer_" + spec["reducer"], path)
    mod = importlib.util.module_from_spec(modspec)
    sys.path.insert(0, BENCH)
    try:
        modspec.loader.exec_module(mod)
    finally:
        sys.path.remove(BENCH)
    return mod.reduce({"snap0": snap0, "snap1": snap1}, spec.get("params", {}))


def test_roles_and_native_sum_to_the_process_clock_over_a_busy_second():
    workers = [Worker("codec-svc"), Worker("access-read_0")]
    try:
        before0 = time.process_time()
        snap0 = {"t": time.monotonic(), "counters": scrape()}
        after0 = time.process_time()
        for w in workers:
            w.burn(0.4)
        spin(0.4)
        before1 = time.process_time()
        snap1 = {"t": time.monotonic(), "counters": scrape()}
        after1 = time.process_time()
    finally:
        for w in workers:
            w.end()
    grew = {r: snap1["counters"][role_series(r)] - snap0["counters"][role_series(r)] for r in trace.ROLES}
    assert all(v >= 0 for v in grew.values()), grew
    # a scrape reads the clocks somewhere inside its render: the process clock
    # grew by at least before1 - after0 and at most after1 - before0 between the two
    assert 0.99 * (before1 - after0) <= sum(grew.values()) <= 1.01 * (after1 - before0)
    assert grew["codec"] == pytest.approx(workers[0].ran, rel=0.02)
    assert grew["io"] == pytest.approx(workers[1].ran, rel=0.02)
    assert grew["other"] >= 0.4
    # the benchmark's files read the same partition: cores x window = the
    # process clock's growth, and the role shares with native sum to it
    span = snap1["t"] - snap0["t"]
    cores = reduce("host_cpu_cores", snap0, snap1)
    assert cores * span == pytest.approx(sum(grew.values()), rel=1e-9)
    assert cores * span == pytest.approx(before1 - after0, rel=0.02)
    pct = {r: reduce("cpu_%s_pct" % r, snap0, snap1) for r in ROLE_PCT}
    assert pct == {r: pytest.approx(100.0 * grew[r] / span) for r in ROLE_PCT}
    rest = 100.0 * sum(grew[r] for r in trace.ROLES if r not in ROLE_PCT) / span
    assert sum(pct.values()) + rest == pytest.approx(100.0 * cores, rel=1e-9)


@pytest.mark.parametrize("name,role", [("repair-io_0", "repair"), ("evw-http-test-0", "request")])
def test_role_counter_never_decreases_when_a_thread_ends(name, role):
    w = Worker(name)
    w.burn(0.2)
    seen = cpu_by_role()[role]
    assert seen >= 0.2
    w.end()
    after_exit = cpu_by_role()[role]
    assert after_exit >= seen  # its last reading is kept in the role's retired sum
    again = Worker(name)  # a new thread's clock starts at 0: it adds, it does not replace
    try:
        again.burn(0.1)
        assert cpu_by_role()[role] >= after_exit + 0.1
    finally:
        again.end()
    assert cpu_by_role()[role] >= after_exit + 0.1


def test_only_a_scrape_reads_the_thread_clocks(tmp_path):
    """Stages, marks, a PUT and a GET leave the series where the last scrape
    put them: nothing but render_all() runs the collector."""
    from chubaofs_tpu.blobstore.cluster import MiniCluster

    before = cpu_by_role()
    c = MiniCluster(str(tmp_path), n_nodes=9, disks_per_node=2)
    try:
        data = bytes(range(256)) * 512
        assert c.access.get(c.access.put(data)) == data
        with trace.stage("access.alloc"), trace.mark("chunk.write"):
            spin(0.05)
    finally:
        c.close()
    assert {r: trace._cpu_series[r].value for r in trace.ROLES} == before
    assert sum(cpu_by_role().values()) >= sum(before.values()) + 0.05


# -- a stage's CPU: under a session, and at no other time --------------------------


def stage_counters(name: str) -> tuple[float, float, float]:
    m = scrape()
    return (m.get('cfs_trace_stage_cpu_seconds{stage="%s"}' % name, 0.0),
            m.get('cfs_trace_stage_seconds_sum{stage="%s"}' % name, 0.0),
            m.get('cfs_trace_stage_seconds_count{stage="%s"}' % name, 0.0))


def test_no_session_no_cpu_reading(monkeypatch):
    """With no profiler session a stage reads no CPU clock and its CPU series
    stands still while its wall series grows."""
    prof = sys.modules.get("jax.profiler")
    assert prof is None or not prof.TraceAnnotation.is_enabled()

    class Clock:
        def __getattr__(self, name):
            return getattr(time, name)

        def thread_time(self):
            raise AssertionError("a stage read the thread's CPU clock outside a session")

    a = stage_counters("access.gather")
    monkeypatch.setattr(trace, "time", Clock())
    with trace.stage("access.gather"):
        spin(0.02)
    monkeypatch.undo()
    b = stage_counters("access.gather")
    assert b[0] == a[0] and b[2] == a[2] + 1 and b[1] - a[1] >= 0.02


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax.profiler as prof

    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    prof.start_trace(str(tmp_path_factory.mktemp("trace")), profiler_options=opts)
    yield
    prof.stop_trace()


@pytest.mark.parametrize("what", ["spin", "sleep"])
def test_stage_cpu_under_a_session(session, what):
    a = stage_counters("access.prepare")
    with trace.stage("access.prepare"):
        ran = spin(0.3) if what == "spin" else time.sleep(0.3)
    b = stage_counters("access.prepare")
    cpu, wall = b[0] - a[0], b[1] - a[1]
    assert b[2] - a[2] == 1 and wall >= 0.3
    if what == "sleep":
        assert cpu < 0.05 * wall, (cpu, wall)
    else:
        # what the thread ran, as the thread itself read it; on an idle host
        # that is its wall time to a per cent, and under this suite's other
        # workers and threads (rivals for the cores and the interpreter lock:
        # the very gap the counter exists to show) never under half of it
        assert cpu == pytest.approx(ran, rel=0.02) and 0.5 * wall <= cpu <= 1.0001 * wall, (cpu, ran, wall)


def test_mark_and_observed_stage_read_no_cpu_under_a_session(session):
    a = scrape()
    with trace.mark("chunk.write"):
        spin(0.02)
    trace.observe_stage("codec.queue_wait", time.perf_counter() - 0.02, 0.02)
    b = scrape()
    cpu = {k: v - a.get(k, 0.0) for k, v in b.items() if k.startswith("cfs_trace_stage_cpu_seconds{")}
    assert not any(cpu.values()), cpu
    assert 'cfs_trace_stage_cpu_seconds{stage="codec.queue_wait"}' not in b
    assert b['cfs_trace_stage_seconds_count{stage="codec.queue_wait"}'] == \
        a.get('cfs_trace_stage_seconds_count{stage="codec.queue_wait"}', 0.0) + 1


# -- the eighteen layer files, and PR 45's three -------------------------------------------------------

PUT_CELLS = ["az1.put16m", "az3.put16m", "az2.put16m"]
GET_CELLS = ["az1.get16m-nodedown", "az2.get16m-azdown", "az1.get16m-rebuild"]
SMALL = ["az1.small-open"]
EXPIRE = ["az1.put16m-expire"]  # PR 45: its own sum of NINE roles, and the new role's share
LAYERS = (
    [("host_cpu_cores", PUT_CELLS, "put_MBps"), ("get_host_cpu_cores", GET_CELLS, "get_MBps"),
     ("small_host_cpu_cores", SMALL, "op_p95_ms")]
    + [("cpu_%s_pct" % r, PUT_CELLS, "put_MBps") for r in ROLE_PCT]
    + [("get_cpu_%s_pct" % r, GET_CELLS, "get_MBps") for r in ROLE_PCT]
    + [("small_cpu_tick_pct", SMALL, "op_p95_ms"),
       ("rebuild_cpu_repair_pct", ["az1.get16m-rebuild"], "get_MBps"),
       ("codec_dispatch_cpu_share", PUT_CELLS, "put_MBps"),
       ("get_codec_dispatch_cpu_share", GET_CELLS, "get_MBps"),
       ("get_decode_wait_cpu_ms", ["az2.get16m-azdown", "az1.get16m-rebuild"], "get_MBps"),
       ("expire_host_cpu_cores", EXPIRE, "put_MBps"), ("expire_cpu_tick_pct", EXPIRE, "put_MBps"),
       ("expire_cpu_reclaim_pct", EXPIRE, "put_MBps")])
# what the daemon of the parent commit renders: stages and jobs, no CPU series
PARENT = {"cfs_codec_jobs_total": 90.0,
          **{'cfs_trace_stage_seconds_%s{stage="%s"}' % (kind, s): 1.0
             for kind in ("sum", "count") for s in trace.STAGES}}


def test_there_are_eighteen_and_the_expiry_cells_three():
    assert len(LAYERS) == len({n for n, _, _ in LAYERS}) == 18 + 3
    # the eight-role sums of the older cells are files this PR may not edit: the new role is
    # summed in the new cell's file alone, and reads ~0 where the reclaim worker sleeps
    assert layer("expire_host_cpu_cores")["params"]["num"] == [role_series(r) for r in trace.ROLES]
    for name in ("host_cpu_cores", "get_host_cpu_cores", "small_host_cpu_cores"):
        assert layer(name)["params"]["num"] == [role_series(r) for r in trace.ROLES if r != "reclaim"]


@pytest.mark.parametrize("name,cells,moves", LAYERS)
def test_layer_file_is_the_benchmarks_entry_and_reads_nothing_from_the_parent(name, cells, moves):
    spec = layer(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == name)
    assert entry == {k: spec[k] for k in entry}
    assert (entry["workloads"], entry["moves"], entry["source"]) == (cells, moves, "program_counter")
    first_new = next(i for i, e in enumerate(bench["per_layer"]) if e["name"] == LAYERS[0][0])
    assert bench["per_layer"].index(entry) >= first_new, "appended, not inserted"
    assert entry["layer"] in {e["layer"] for e in bench["per_layer"][:first_new]}
    reports = next(m for m in bench["end_to_end"] if m["name"] == moves)["workloads"]
    assert set(cells) <= set(reports)
    assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
    assert "reads nothing" in spec["what"]
    series = spec["params"]["num"] + spec["params"].get("den", [])
    assert all(s.split("{")[0] in ("cfs_proc_cpu_seconds", "cfs_trace_stage_cpu_seconds",
                                   "cfs_trace_stage_seconds_sum", "cfs_trace_stage_seconds_count")
               and s.split('"')[1] in set(trace.ROLES) | trace.STAGES for s in series)
    # the parent of this PR: no such series, so nothing to read and no raise
    snap0 = {"t": 10.0, "counters": dict(PARENT)}
    snap1 = {"t": 61.0, "counters": {k: v + 5.0 for k, v in PARENT.items()}}
    assert reduce(name, snap0, snap1) is None
    # this program: the series are there and the reading is a number
    grown = {s: 2.0 for s in series if s not in PARENT}
    value = reduce(name, snap0, {"t": 61.0, "counters": {**snap1["counters"], **grown}})
    assert isinstance(value, float) and value > 0
