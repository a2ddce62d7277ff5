"""The daemon's own stages on the profiler's clock.

The program wraps its stages in jax.profiler.TraceAnnotation("cfs:<stage>")
(chubaofs_tpu/blobstore/trace.py), so a traced window's "/host:CPU" plane holds
one event per stage per thread, on the clock of the device's "XLA Ops" line.
xplane.load keeps only "bench:" names and the reducers' ctx carries no path,
so this module finds the run's trace itself: run.py copies it to
.bench_run/<--workload>/out/window.xplane.pb just before the reducers run.

An event is (stage, start_s, end_s, line, req): `line` is the index of the
thread's line in the plane (thread lines are all named alike), `req` the
request's trace id where the stage ran under a request span, else None.

project() flattens the events to one non-overlapping labelling of the window,
for xplane.attribute(): what the dispatcher thread was in, and where it was
in nothing, what the request path was waiting in."""

from __future__ import annotations

import glob
import os
import sys

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
PREFIX = "cfs:"
DISPATCHER = ("codec.", "hostbatch.")  # the one codec dispatcher thread's stages
BACKGROUND = "scheduler."  # the background tick; everything under it is its own
WRAPPERS = ("gateway.handle", "access.put", "access.get")  # a request is open
# a thread in one of these only waits for other threads' futures: it names a
# moment only where no thread is in a stage that does the work
WAITING = ("access.encode_wait", "access.decode_wait", "access.write_stripe",
           "access.read", "access.gather")


def trace_path() -> str | None:
    """This run's copy of the trace, by the --workload this process was given;
    else the newest copy under .bench_run/."""
    argv = sys.argv
    cell = next((a.split("=", 1)[1] for a in argv if a.startswith("--workload=")), None)
    if cell is None and "--workload" in argv[:-1]:
        cell = argv[argv.index("--workload") + 1]
    root = os.path.join(os.path.dirname(HERE), ".bench_run")
    if cell is not None:
        path = os.path.join(root, cell, "out", "window.xplane.pb")
        return path if os.path.exists(path) else None
    found = glob.glob(os.path.join(root, "*", "out", "window.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> list[tuple]:
    """Every cfs: event of the host plane, in no particular order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name[len(PREFIX):], e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, i,
                                dict(e.stats).get("req")))
    return out


def innermost(events: list[tuple]) -> list[tuple]:
    """Per thread, the stage it was innermost in over time: non-overlapping
    (stage, start_s, end_s, line, root) segments, `root` being the outermost
    stage open on that thread then. Stages of one thread nest (they are
    context managers), so a stack per line is enough."""
    by_line: dict[int, list] = {}
    for name, s, e, line, _ in events:
        by_line.setdefault(line, []).append((s, -e, name))
    out = []
    for line, evs in by_line.items():
        evs.sort()
        stack: list[tuple] = []  # (name, end)
        at = 0.0

        def emit(until: float) -> None:
            if stack and until > at:
                out.append((stack[-1][0], at, until, line, stack[0][0]))

        for s, neg_e, name in evs:
            while stack and stack[-1][1] <= s:
                emit(stack[-1][1])
                at = max(at, stack[-1][1])
                stack.pop()
            emit(s)
            at = s
            stack.append((name, -neg_e))
        while stack:
            emit(stack[-1][1])
            at = max(at, stack[-1][1])
            stack.pop()
    return out


def project(events: list[tuple], lo: float, hi: float,
            segments: list[tuple] | None = None) -> list[tuple]:
    """[(label, start_s, end_s)] covering [lo, hi] without overlap:
    cfs:<stage>          the dispatcher thread was in <stage>;
    cfs:idle/empty       it was in nothing and no request was inside the daemon;
    cfs:wait/<stage>     a request was, and <stage> is the innermost stage that
                         most request-path threads were in (below the wrappers;
                         a stage that works before one that waits on futures);
    cfs:wait/unnamed     a request was, and no thread was below a wrapper:
                         time the stages do not reach.
    ``segments`` is innermost(events), where the caller has it already."""
    marks: list[tuple] = []  # (time, +1/-1, kind, stage)
    for name, s, e, _, root in segments or innermost(events):
        s, e = max(s, lo), min(e, hi)
        if e <= s or root.startswith(BACKGROUND):
            continue
        if name not in WRAPPERS:
            kind = "disp" if name.startswith(DISPATCHER) else "req"
            marks += [(s, 1, kind, name), (e, -1, kind, name)]
    for name, s, e, _, _ in events:  # a request is open wherever a wrapper is
        s, e = max(s, lo), min(e, hi)
        if e > s and name in WRAPPERS:
            marks += [(s, 1, "open", name), (e, -1, "open", name)]
    marks.sort(key=lambda m: (m[0], m[1]))
    disp: dict[str, int] = {}
    req: dict[str, int] = {}
    open_requests = 0
    out: list[list] = []
    at = lo

    def label() -> str:
        if disp:
            return PREFIX + max(disp, key=disp.get)
        if not open_requests:
            return PREFIX + "idle/empty"
        if not req:
            return PREFIX + "wait/unnamed"
        return PREFIX + "wait/" + max(req, key=lambda n: (n not in WAITING, req[n]))

    marks.append((hi, 0, "", ""))  # closes the last interval
    for t, step, kind, name in marks:
        if t > at:
            lab = label()
            if out and out[-1][0] == lab and out[-1][2] == at:
                out[-1][2] = t
            else:
                out.append([lab, at, t])
            at = t
        if kind == "open":
            open_requests += step
        elif kind:
            held = disp if kind == "disp" else req
            held[name] = held.get(name, 0) + step
            if not held[name]:
                del held[name]
    return [tuple(x) for x in out]


def overlap_seconds(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Seconds in both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def thread_seconds(segments: list[tuple], lo: float, hi: float) -> dict[str, float]:
    """Seconds threads spent innermost in each stage inside [lo, hi]."""
    out: dict[str, float] = {}
    for name, s, e, _, _ in segments:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out
