"""Growth of the counters ``names`` over the window per user byte the client
had acknowledged between the two snapshots, times ``scale``."""
from readers import delta, user_bytes


def reduce(ctx, params):
    got = user_bytes(ctx, params["kind"])
    return params.get("scale", 1.0) * delta(ctx, params["names"]) / got if got else None
