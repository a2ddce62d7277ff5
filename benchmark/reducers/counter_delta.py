"""Plain growth of the counters ``names`` over the window."""
from readers import delta


def reduce(ctx, params):
    return delta(ctx, params["names"])
