"""Percentile ``q`` of send-to-acknowledge time of the ops of one kind that
start and finish inside the window, on the client's clock."""
import window


def reduce(ctx, params):
    ops = window.in_window(ctx["ops"], ctx["t0"], ctx["t1"], params["kind"])
    return window.percentile([(o["t_end"] - o["t_start"]) * 1e3 for o in ops], params["q"])
