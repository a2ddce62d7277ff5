"""``scale`` times the growth of the counters ``num`` over the window, per
second of the window, or per unit of growth of the counters ``den`` where
given. A program that renders none of ``num`` (the parent of the PR that added
them) reads None, not 0."""
from readers import delta


def reduce(ctx, params):
    if not any(n in ctx["snap1"]["counters"] for n in params["num"]):
        return None
    per = delta(ctx, params["den"]) if params.get("den") else ctx["snap1"]["t"] - ctx["snap0"]["t"]
    return params.get("scale", 1.0) * delta(ctx, params["num"]) / per if per else None
