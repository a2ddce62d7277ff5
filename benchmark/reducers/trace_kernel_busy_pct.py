"""Device time of the fused GF kernel's events as % of the traced window."""
from readers import kernel_events


def reduce(ctx, params):
    if ctx["trace"] is None:
        return None
    calls, span = kernel_events(ctx["trace"])
    return 100.0 * sum(t for _, t in calls) / span / max(1, len(ctx["trace"]["devices"]))
