"""How much of the device's idle time the daemon's stages reach:
100 x (1 - idle time labelled cfs:wait/unnamed / idle time of the window).

Also hands the projection of the daemon's stages (hostspans.project) to
xplane.attribute() by appending it to the trace's annotations, so the
breakdown names each idle gap, and says, for the ten longest gaps, the
thread-seconds by stage and the scheduler.* span that overlapped each. A
program without stages on the profiler's clock reads None."""
import os

import hostspans
import xplane


def reduce(ctx, params):
    trace = ctx["trace"]
    path = hostspans.trace_path() if trace is not None else None
    events = hostspans.load(path) if path else []
    if not events:
        return None
    lo, hi = xplane.window_of(trace, "bench:window")
    segments = hostspans.innermost(events)
    labels = hostspans.project(events, lo, hi, segments)
    if not trace.get("cfs_projected"):  # a cell may list two metrics of this reducer
        trace["annotations"].extend(labels)
        trace["cfs_projected"] = True
    device = [e for evs in trace["devices"].values() for e in evs]
    idle = sorted(xplane.gaps(device, lo, hi))
    idle_s = sum(e - s for s, e in idle)
    spans_of: dict = {}
    for n, s, e in labels:
        spans_of.setdefault(n, []).append((s, e))
    by_label = {n: hostspans.overlap_seconds(idle, ivs) for n, ivs in spans_of.items()}
    ticks = [ev for ev in events if ev[0].startswith(hostspans.BACKGROUND)]
    longest = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        secs = hostspans.thread_seconds(segments, s, e)
        longest.append({
            "at_s": s - lo, "seconds": e - s,
            "what": xplane.attribute((s, e), trace, "bench:window"),
            "thread_seconds": dict(sorted(secs.items(), key=lambda kv: -kv[1])[:8]),
            "background": sorted({t[0] for t in ticks if t[1] < e and t[2] > s})})
    a, b = ctx["snap0"]["counters"], ctx["snap1"]["counters"]
    grew = {k: v - a.get(k, 0.0) for k, v in b.items() if k.startswith("cfs_trace_stage_seconds_")}
    ctx["say"](stage_seconds_and_count={
        k.split('"')[1]: [v, grew[k.replace("_sum{", "_count{")]]
        for k, v in grew.items() if "_sum{" in k})
    ctx["say"](host_spans=len(events), trace_bytes=os.path.getsize(path),
               idle_seconds_by_label=dict(sorted(by_label.items(), key=lambda kv: -kv[1])[:12]),
               longest_idle_gaps=longest)
    return 100.0 * (1.0 - by_label.get("cfs:wait/unnamed", 0.0) / idle_s) if idle_s else None
