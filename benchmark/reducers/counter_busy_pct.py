"""Seconds the counters ``names`` grew over the window, as % of the window."""
from readers import delta


def reduce(ctx, params):
    span = ctx["snap1"]["t"] - ctx["snap0"]["t"]
    return 100.0 * delta(ctx, params["names"]) / span
