"""What the gauges ``names`` read at the window's close (summed): a level, not
a growth. A program that renders none of them reads None, not 0."""


def reduce(ctx, params):
    at_close = ctx["snap1"]["counters"]
    if not any(n in at_close for n in params["names"]):
        return None
    return params.get("scale", 1.0) * sum(at_close.get(n, 0.0) for n in params["names"])
