"""Bytes the blobnodes' chunk files grew by over the window per user byte
acknowledged in it (headers and crc framing included)."""
from readers import user_bytes


def reduce(ctx, params):
    got = user_bytes(ctx, "put")
    return (ctx["snap1"]["stored"] - ctx["snap0"]["stored"]) / got if got else None
