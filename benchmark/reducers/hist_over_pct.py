"""Share (%) of the observations the summary ``summary`` took over the window
that lie above its bucket edge ``le`` (the edge as /metrics prints it)."""
from readers import delta


def reduce(ctx, params):
    name = params["summary"]
    count = delta(ctx, [name + "_count"])
    under = delta(ctx, ['%s_bucket{le="%s"}' % (name, params["le"])])
    return 100.0 * (count - under) / count if count else None
