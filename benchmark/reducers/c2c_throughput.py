"""Completion-to-completion throughput of one op kind (benchmark/window.py),
times ``scale`` (1e-6 for MB/s)."""
import window


def reduce(ctx, params):
    v = window.c2c_bytes_per_s(ctx["ops"], ctx["t0"], ctx["t1"], params["kind"])
    return None if v is None else v * params.get("scale", 1.0)
