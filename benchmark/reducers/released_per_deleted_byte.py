"""Bytes the blobnodes released over the window (the growth of the counters
``released``: punched out, or dropped by a compaction) per stored byte of the
objects whose DELETE (ops of kind ``kind``) was acknowledged between the two
snapshots. An object's stored bytes are reference_expire.stored_bytes(): every
shard of every blob as a chunk record, from the configuration's policies,
modes and record_framing; an object's size is the traffic's object_bytes. A
program that renders none of the counters, or a window without a DELETE, reads
None."""
import reference_expire
from readers import delta


def reduce(ctx, params):
    if not any(n in ctx["snap1"]["counters"] for n in params["released"]):
        return None
    lo, hi = ctx["snap0"]["t"], ctx["snap1"]["t"]
    deleted = sum(1 for o in ctx["ops"] if o["ok"] and o["kind"] == params["kind"] and lo <= o["t_end"] <= hi)
    each = reference_expire.stored_bytes(ctx["traffic"]["params"]["object_bytes"], ctx["config"])
    ctx["say"](deletes_acknowledged=deleted, stored_bytes_an_object=each,
               released_bytes=delta(ctx, params["released"]))
    return delta(ctx, params["released"]) / (deleted * each) if deleted else None
