"""Distributed tracing — spans with in-band propagation + RPC track logs.

Reference counterpart: blobstore/common/trace (tracer.go:34 opentracing
aliases, span.go:25-35) — every blobstore ctx carries a span; services append
"track log" entries (module:latency/result) that ride response headers so the
access gateway can log one line covering the whole fan-out (used at
access/stream_put.go:47,100). Kept: trace-id propagation, child spans, track
logs appended bottom-up. The carrier is a plain dict standing in for HTTP
headers (inject/extract), so both in-process and HTTP hops propagate the same
way; the packet TCP wire carries the same two fields in its arg blob
(proto/packet.py trace_inject/trace_reply).

Track logs are BOUNDED: at most TRACK_MAX entries per span (a failpoint-looped
fan-out must not blow the response-header budget), and module names are
sanitized (`;`/newlines/`:` would corrupt the ';'-joined wire form).

Beyond the wire-form track log, every span is a STRUCTURED record: a span id,
its parent (in-process parent span, or the remote caller's span id carried
next to the trace id), a wall-clock start stamp plus monotonic duration, and
named STAGES — (name, offset, duration) attributions inside the span
(codec queue wait, raft commit wait, pool checkout...) that the
critical-path analyzer (tools/cfstrace.py) projects onto the request's wall
time. `finish()` hands the span to the trace sink (utils/tracesink.py) when
one is installed; with no sink the hook is a single None check.

`stage(name)` is how a stage gets recorded: a context manager entered where
the work happens, which on exit lands one pair of clock reads in three places
— the profiler's clock (a jax.profiler.TraceAnnotation "cfs:<name>" on the
/host:CPU plane, beside the device's "XLA Ops" line, whenever a profiler
session is on and at no other time), the always-on counter
cfs_trace_stage_seconds{stage=<name>}, and the current request Span's stages.
`observe_stage` is the same without the annotation, for intervals that belong
to no thread (a job's time in a queue); `mark` is the annotation alone, for
the per-shard steps whose always-on cost would show (MARKS; ~1 us with no
session, against ~3 for a stage). STAGES is the closed set of counted names.
This module never imports jax: a process that has not imported it has no
profiler to share a clock with.

Wall time says how long a stage LASTED; two readings of the kernel's
per-thread CPU clock say how long its thread RAN. Under a profiler session,
and at no other time, a `stage` also adds its thread's CPU seconds to
cfs_trace_stage_cpu_seconds{stage=<name>} (the series stands still outside a
session; `observe_stage` and `mark` have no thread to read). And whenever
/metrics is rendered, `collect_cpu` reads every live thread's CPU clock and
sums it by thread ROLE into cfs_proc_cpu_seconds{role=<role>}: who the
process's cores were spent on, at no cost between two scrapes.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid

from chubaofs_tpu.utils import exporter

TRACE_ID_KEY = "Trace-Id"
TRACK_LOG_KEY = "Trace-Tracklog"
SPAN_ID_KEY = "Trace-Span-Id"

# hard cap on track entries per span: deep fan-outs degrade to a truncated
# track log, never to an unbounded response header
TRACK_MAX = 64
# stage attributions are richer than track entries but just as bounded: a
# retry-looped hop must not grow a span record without limit
STAGE_MAX = 128
_ENTRY_MAX = 128  # one hostile module name must not be the whole header

# sink hook installed by utils/tracesink (None = tracing-only, zero
# persistence work); called with the finished span, must never raise
_finish_hook = None


def set_finish_hook(fn) -> None:
    """Install (or clear, with None) the span-finish hook the trace sink
    rides. Process-global, like the span machinery itself."""
    global _finish_hook
    _finish_hook = fn


def finish_hook():
    """The currently installed span-finish hook (None if none) — a caller
    that temporarily swaps its own hook in must save this and CHAIN to it,
    or an active trace sink silently loses every span it swallows."""
    return _finish_hook


def union_len(intervals) -> float:
    """Total length of the union of [s, e) intervals (overlap counts once).
    THE sweep-line both overlap consumers share — the scheduler's
    repair-span overlap ratio and cfs-trace's critical-path/stage-overlap
    analyzers must agree on this math or their reported ratios drift."""
    total = 0.0
    end = float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def intersect_len(a, b) -> float:
    """Length of the intersection of two interval unions (inclusion-
    exclusion over union_len): how long BOTH families were active at once."""
    if not a or not b:
        return 0.0
    return union_len(a) + union_len(b) - union_len(list(a) + list(b))


def overlap_ratio(a, b) -> float | None:
    """Intersection of two interval-union families over the SMALLER union —
    1.0 means the lesser family ran entirely inside the greater (perfect
    pipelining), 0.0 means strictly back-to-back, None means either side
    never happened. THE ratio definition shared by the scheduler's
    repair-span metric and cfs-trace's --overlap report: one implementation
    so the dashboard number and the CLI report can never drift apart."""
    if not a or not b:
        return None
    floor = min(union_len(a), union_len(b))
    return (intersect_len(a, b) / floor) if floor > 0 else 0.0

_local = threading.local()

_SANITIZE = str.maketrans({";": "_", ":": "_", "\n": "_", "\r": "_"})
# a whole entry keeps its own "module:ms" colon; only the separators that
# would corrupt the ';'-joined wire form are rewritten
_SANITIZE_ENTRY = str.maketrans({";": "_", "\n": "_", "\r": "_"})


def sanitize_module(module: str) -> str:
    """Track-log entries are ';'-joined and ':'-split downstream; a module
    name carrying either (or newlines, which break log lines) is rewritten."""
    return str(module).translate(_SANITIZE)[:_ENTRY_MAX]


class Span:
    def __init__(self, operation: str, trace_id: str | None = None,
                 parent: "Span | None" = None):
        self.operation = operation
        # lazy: the id mints on first READ. Dispatch loops create a span per
        # packet/VFS op unconditionally; an untraced op whose id nobody asks
        # for must not pay os.urandom entropy on the hot path.
        self._trace_id = trace_id or (parent.trace_id if parent else None)
        self.parent = parent
        self.start = time.perf_counter()
        # wall stamp pairs records from different processes onto one
        # timeline (same-host skew only); NEVER used for durations — those
        # stay on the monotonic clock
        self.start_wall = time.time()
        self.tags: dict[str, object] = {}
        self.track: list[str] = []  # track-log entries, e.g. "blobnode:12"
        self.track_dropped = 0  # entries the TRACK_MAX cap swallowed
        # named in-span attributions: (name, offset_s from start, dur_s)
        self.stages: list[tuple[str, float, float]] = []
        self.stage_dropped = 0
        # span id of the remote CALLER's span when this span continued a
        # carrier that named one (the cross-process parent edge)
        self.remote_parent: str | None = None
        self._span_id: str | None = None
        self.finished_us: int | None = None

    @property
    def trace_id(self) -> str:
        if self._trace_id is None:
            self._trace_id = uuid.uuid4().hex[:16]
        return self._trace_id

    @property
    def span_id(self) -> str:
        # lazy like trace_id: minted only when someone records/propagates it
        if self._span_id is None:
            self._span_id = uuid.uuid4().hex[:16]
        return self._span_id

    # -- opentracing-style surface ---------------------------------------------
    def set_tag(self, k: str, v) -> "Span":
        self.tags[k] = v
        return self

    def _push_track(self, entry: str):
        if len(self.track) >= TRACK_MAX:
            if self.track_dropped == 0:
                # first drop on this span: count it (cold path — truncation
                # is the anomaly the counter exists to surface)
                try:
                    from chubaofs_tpu.utils.exporter import registry

                    registry("trace").counter("track_truncated").add()
                except Exception:
                    pass
            self.track_dropped += 1
            return
        self.track.append(entry)

    def add_stage(self, name: str, start: float, dur: float | None = None):
        """Attribute a named stage of this span: `start` is a
        time.perf_counter() stamp (any thread — one global clock), `dur`
        seconds (elapsed-since-start when omitted). Bounded by STAGE_MAX."""
        if dur is None:
            dur = time.perf_counter() - start
        if len(self.stages) >= STAGE_MAX:
            self.stage_dropped += 1
            return
        self.stages.append((sanitize_module(name), start - self.start, dur))

    def append_track_log(self, module: str, start: float | None = None,
                         err: Exception | None = None):
        """stream_put.go:100-style: module + elapsed ms + error class."""
        ms = int(((time.perf_counter() - (start or self.start)) * 1000))
        entry = f"{sanitize_module(module)}:{ms}"
        if err is not None:
            entry += f"/{sanitize_module(type(err).__name__)}"
        self._push_track(entry)

    def merge_track(self, entries):
        """Fold a remote hop's track entries (list or ';'-joined string) into
        this span, sanitized and bounded — the client side of a reply that
        carried a track log back."""
        if not entries:
            return
        if isinstance(entries, str):
            entries = entries.split(";")
        for e in entries:
            e = str(e).translate(_SANITIZE_ENTRY)[:_ENTRY_MAX]
            if e:
                self._push_track(e)

    def finish(self):
        if self.finished_us is None:
            self.finished_us = int((time.perf_counter() - self.start) * 1e6)
            if self.parent is not None:
                for e in self.track:
                    self.parent._push_track(e)
                self.parent.track_dropped += self.track_dropped
            hook = _finish_hook
            if hook is not None:
                try:
                    hook(self)
                except Exception:
                    pass  # a sink failure must never fail the traced op

    def __enter__(self):
        push_span(self)
        return self

    def __exit__(self, et, ev, tb):
        self.finish()
        pop_span()
        return False

    # -- propagation -----------------------------------------------------------
    def track_entries(self) -> list[str]:
        """Track entries as they go on the wire (always a fresh list — a
        caller may attach it to a reply that outlives this span's next
        append): a dropped-entry count is no longer silent — the
        `...truncated:<n>` sentinel rides in-band so a reader knows the log
        is a prefix, not the whole story."""
        if self.track_dropped:
            return self.track + [f"...truncated:{self.track_dropped}"]
        return list(self.track)

    def inject(self, carrier: dict):
        carrier[TRACE_ID_KEY] = self.trace_id
        carrier[SPAN_ID_KEY] = self.span_id
        if self.track:
            carrier[TRACK_LOG_KEY] = ";".join(self.track_entries())

    def track_log_string(self) -> str:
        return ";".join(self.track_entries())

    def modules(self) -> set[str]:
        """Distinct module names present in the track log."""
        return {e.split(":", 1)[0] for e in self.track if e}

    def to_record(self) -> dict:
        """The span as a JSON-able SpanRecord — what the trace sink persists
        and /traces serves; tools/cfstrace.py reassembles trees from these."""
        dur = self.finished_us
        if dur is None:  # unfinished span recorded early (best effort)
            dur = int((time.perf_counter() - self.start) * 1e6)
        rec: dict = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": (self.parent.span_id if self.parent is not None
                               else self.remote_parent),
            "op": self.operation,
            "start": round(self.start_wall, 6),
            "dur_us": dur,
        }
        if self.stages:
            rec["stages"] = [[n, int(off * 1e6), int(d * 1e6)]
                             for n, off, d in self.stages]
        if self.stage_dropped:
            rec["stages_dropped"] = self.stage_dropped
        if self.tags:
            rec["tags"] = dict(self.tags)
        if self.track:
            rec["track"] = self.track_log_string()
        return rec


def extract_trace_id(carrier: dict | None) -> str | None:
    """Trace id from a carrier dict, tolerant of lower-cased header keys
    (rpc Request lower-cases everything)."""
    if not carrier:
        return None
    return carrier.get(TRACE_ID_KEY) or carrier.get(TRACE_ID_KEY.lower())


def extract_span_id(carrier: dict | None) -> str | None:
    """The remote caller's span id, same lower-case tolerance."""
    if not carrier:
        return None
    return carrier.get(SPAN_ID_KEY) or carrier.get(SPAN_ID_KEY.lower())


def start_span(operation: str, carrier: dict | None = None) -> Span:
    """New root (or remote-continued, when carrier holds a trace id) span."""
    span = Span(operation, trace_id=extract_trace_id(carrier))
    if carrier:
        span.remote_parent = extract_span_id(carrier)
        tl = carrier.get(TRACK_LOG_KEY) or carrier.get(TRACK_LOG_KEY.lower())
        if tl:
            span.merge_track(tl)
    return span


def child_of(parent: Span | None, operation: str) -> Span:
    return Span(operation, parent=parent) if parent else Span(operation)


def push_span(span: Span):
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    stack.append(span)


def pop_span():
    stack = getattr(_local, "stack", None)
    if stack:
        stack.pop()


def current_span() -> Span | None:
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


# -- stages: profiler's clock + counter + request record -----------------------

# <layer>.<what>; each name is used on one kind of thread only (HTTP worker,
# access-pipe stage, access write worker, codec dispatcher, background tick,
# repair worker and its stripe pool, reclaim worker and its pool),
# so a name also says which thread. The set is closed: it is the declared
# value set of the counter's `stage` label (exporter._check_bounded).
STAGES = frozenset((
    "gateway.recv", "gateway.queue", "gateway.handle", "gateway.send",
    "access.put", "access.get", "access.prepare", "access.alloc",
    "access.encode_wait", "access.decode_wait", "access.write_stripe",
    "access.pool_wait", "access.read", "access.gather", "access.assemble",
    "codec.queue_wait", "codec.drain", "codec.stack", "codec.expand",
    "codec.concat", "codec.deliver",
    "hostbatch.group", "hostbatch.launch", "hostbatch.fetch",
    "scheduler.tick", "scheduler.scrub", "scheduler.inspect",
    "repair.gather", "repair.gather_local", "repair.decode_wait",
    "repair.write_back", "repair.commit",
    # the served DELETE (HTTP worker) and the reclaim plane (its own worker
    # and pool): one drain of the blob_delete topic, a DELETE's
    # acknowledgement -> its last unit punched (observed, no thread's), one
    # chunk's batch of a phase and, inside it, its wait for the chunk lock
    # (observed: where the deleter and the writers meet), a compaction's copy
    # outside the chunk lock and its catch-up + swap under it
    "access.delete", "deleter.batch", "deleter.apply", "chunk.delete",
    "chunk.delete_wait", "chunk.compact", "chunk.compact_swap",
))
# per-shard steps, on the profiler's clock only (`mark`): six to sixteen of
# each run per blob, and the background tick reads thousands of shards a
# second, so as counted stages they cost a small-object op a tenth of its
# median (PERF.md, PR 25). blobnode.* keep their own TP summaries
# (cfs_blobnode_shard_put / _get).
MARKS = frozenset((
    "access.sem_wait", "blobnode.put_shard", "blobnode.get_shard",
    "chunk.lock_wait", "chunk.write", "chunk.meta", "chunk.verify",
))

_stage_summaries: dict[str, object] = {}


def _stage_summary(name: str):
    exporter.declare_label_values("stage", STAGES)
    s = _stage_summaries[name] = exporter.registry("trace").summary(
        "stage_seconds", {"stage": name})
    return s


def declare_stages(names) -> None:
    """Make the stages' counters now, at 0: a reader of "this never ran"
    must find a series that says 0, not none (a stage's counter is otherwise
    made by its first run)."""
    for name in names:
        if name not in _stage_summaries:
            _stage_summary(name)


def observe_stage(name: str, start: float, dur: float,
                  span: Span | None = None) -> None:
    """Record a stage that ran [start, start + dur) on perf_counter: the
    counter always, `span`'s record when given. No annotation — for
    intervals no thread was inside (queue waits), stamped after the fact."""
    (_stage_summaries.get(name) or _stage_summary(name)).observe(dur)
    if span is not None:
        span.add_stage(name, start, dur)


_stage_cpu_counters: dict[str, object] = {}


def _stage_cpu_counter(name: str):
    exporter.declare_label_values("stage", STAGES)
    c = _stage_cpu_counters[name] = exporter.registry("trace").counter(
        "stage_cpu_seconds", {"stage": name})
    return c


def _annotate(name: str, span: Span | None, stage: "stage | None" = None):
    """The entered profiler annotation of a stage, or None with no session
    on (or no jax in this process); req= joins one request's stages across
    threads. Under a session a `stage` is also handed its thread's CPU
    clock as the block begins (a `mark` asks for none)."""
    prof = sys.modules.get("jax.profiler")
    if prof is None or not prof.TraceAnnotation.is_enabled():
        return None
    kw = {"req": span.trace_id} if span is not None else {}
    ann = prof.TraceAnnotation("cfs:" + name, **kw)
    ann.__enter__()
    if stage is not None:
        stage._cpu = time.thread_time()
    return ann


class mark:
    """`with trace.mark("chunk.write"): ...` — the profiler's clock only."""

    __slots__ = ("name", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "mark":
        self._ann = _annotate(self.name, None)
        return self

    def __exit__(self, et, ev, tb):
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        return False


class stage:
    """`with trace.stage("access.alloc"): ...` — see the module docstring.
    The request span is the thread's current one. `track` names the module
    of a track-log entry the same interval appends to it when the block
    did not raise (stream_put.go's per-hop `module:ms`)."""

    __slots__ = ("name", "track", "span", "start", "_ann", "_cpu")

    def __init__(self, name: str, track: str | None = None):
        self.name = name
        self.track = track

    def __enter__(self) -> "stage":
        self.span = span = current_span()
        self._ann = _annotate(self.name, span, self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dur = time.perf_counter() - self.start
        if self._ann is not None:
            ran = time.thread_time() - self._cpu
            self._ann.__exit__(et, ev, tb)
            (_stage_cpu_counters.get(self.name) or _stage_cpu_counter(self.name)).add(ran)
        span = self.span
        observe_stage(self.name, self.start, dur, span)
        if span is not None and self.track is not None and et is None:
            span.append_track_log(self.track, start=self.start)
        return False


# -- CPU by thread role: who ran, read where /metrics is rendered -----------------

# The closed value set of cfs_proc_cpu_seconds' `role` label. The first seven
# are the daemon's hot threads by the names the code gives them; `other` is
# every other Python thread (in a benchmark cell the harness's main thread,
# a reload, the sampling profiler's); `native` is the process's CPU clock
# minus all of those: threads Python does not own (PJRT and the TPU runtime,
# the jax profiler's collectors).
ROLES = ("loop", "request", "io", "codec", "tick", "repair", "reclaim", "other", "native")
# thread-name prefix -> role, first match: the one mapping the counter's label
# and the sampling profiler's role totals (utils/profiler.py) both read
_ROLE_OF_PREFIX = (
    ("evloop-", "loop"),  # acceptor and loop shards: receive, reply flush
    ("evw-", "request"),  # HTTP workers: gateway.handle, access.put / get
    ("access-pipe", "request"),  # the blob stages of every request
    ("access-read", "io"), ("access-probe", "io"),
    ("access_", "io"),  # the shard write pool
    ("codec-svc", "codec"),  # the one dispatcher
    ("blobstore-bg", "tick"),
    ("repair-", "repair"),  # repair-worker, repair-stripe*, repair-io*
    ("reclaim-", "reclaim"),  # reclaim-worker (deleter, compaction), reclaim-io*
)


def thread_role(name: str) -> str:
    """The role of a thread by its name (or by its profiler bucket: a
    prefix holds no digit run); `other` where the name is nobody's."""
    for prefix, role in _ROLE_OF_PREFIX:
        if name.startswith(prefix):
            return role
    return "other"


_cpu_lock = threading.Lock()
_cpu_last: dict = {}  # live Thread -> (role, CPU seconds at the last scrape)
_cpu_retired = dict.fromkeys(ROLES[:-1], 0.0)  # of threads that have ended
_cpu_series: dict[str, object] = {}


def collect_cpu() -> None:
    """Refresh cfs_proc_cpu_seconds{role}: every live Python thread's CPU
    clock, summed by role, and the process's clock less their sum as
    `native`. Run by exporter.render_all() and by nothing else; monotone (a
    thread that ended keeps its last reading in its role's retired sum; what
    it ran after that reading shows as `native`). A platform without
    per-thread CPU clocks renders no such series."""
    clock_of = getattr(time, "pthread_getcpuclockid", None)
    if clock_of is None:
        return
    with _cpu_lock:
        if not _cpu_series:
            reg = exporter.registry("proc")
            _cpu_series.update((r, reg.counter("cpu_seconds", {"role": r})) for r in ROLES)
        live = {}
        for t in threading.enumerate():
            try:
                live[t] = (thread_role(t.name), time.clock_gettime(clock_of(t.ident)))
            except (OSError, TypeError):
                continue  # it ended between the two calls: its last reading retires below
        total = time.process_time()
        retired = dict(_cpu_retired)
        for t, (role, ran) in _cpu_last.items():
            if t not in live:
                retired[role] += ran
        by_role = dict(retired)
        for role, ran in live.values():
            by_role[role] += ran  # KeyError: the mapping names a role ROLES does not
        by_role["native"] = total - sum(by_role.values())
        _cpu_retired.update(retired)
        _cpu_last.clear()
        _cpu_last.update(live)
        for role, ran in by_role.items():
            series = _cpu_series[role]
            if ran > series.value:
                series.add(ran - series.value)


exporter.add_collector(collect_cpu)
