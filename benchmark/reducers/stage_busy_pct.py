"""Seconds the stages ``stages`` ran over the window, as % of the window
(counter_busy_pct for cfs_trace_stage_seconds). A program without the stages
reads None, not 0."""
from readers import delta


def reduce(ctx, params):
    names = ['cfs_trace_stage_seconds_sum{stage="%s"}' % s for s in params["stages"]]
    if not any(n in ctx["snap1"]["counters"] for n in names):
        return None
    return 100.0 * delta(ctx, names) / (ctx["snap1"]["t"] - ctx["snap0"]["t"])
