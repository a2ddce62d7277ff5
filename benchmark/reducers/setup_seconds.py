"""Process start to window open: boot, TPU init, cache load, closed-set
warm-up, data load, and the warm-up by traffic."""


def reduce(ctx, params):
    return ctx["setup_s"]
