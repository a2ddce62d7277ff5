"""Percentile ``q`` of the latency of every op due in the window, timed from
when it was due. A failed op has no latency to report: it fails `correct`."""
import window


def reduce(ctx, params):
    return window.percentile(
        window.due_latencies_ms(ctx["ops"], ctx["t0"], ctx["t1"], params.get("kind")), params["q"])
