"""Delta over the window of the counters ``num`` over the delta of ``den``
(e.g. a summary's _sum over its _count)."""
from readers import delta


def reduce(ctx, params):
    den = delta(ctx, params["den"])
    return delta(ctx, params["num"]) / den if den else None
