"""Mean milliseconds over the window: the seconds the stages ``stages`` (or the
counters ``num``) grew by, over the growth of the counters ``den`` (default:
how often the first stage ran). A program that renders none of the numerator's
counters (the parent of the PR that added them) reads None, not 0."""
from readers import delta


def reduce(ctx, params):
    stages = params.get("stages", [])
    num = params.get("num") or ['cfs_trace_stage_seconds_sum{stage="%s"}' % s for s in stages]
    den = delta(ctx, params.get("den") or ['cfs_trace_stage_seconds_count{stage="%s"}' % stages[0]])
    if not den or not any(n in ctx["snap1"]["counters"] for n in num):
        return None
    return 1e3 * delta(ctx, num) / den
