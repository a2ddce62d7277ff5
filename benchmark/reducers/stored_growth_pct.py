"""Growth over the window of the bytes the filesystem holds of the chunk
datafiles, as % of what it held when the window opened: 100 x (the gauge
``gauge`` at the close / at the open - 1). The gauge is a level the program
renders at every scrape (the datafiles' lengths less every byte a punch, an
unlinked extent or a compaction really gave back); the snapshots' `stored`,
deploy.stored_bytes(), is the sum of the lengths, which never falls with a
delete. A program that does not render the gauge, or an empty cluster, reads
None."""


def reduce(ctx, params):
    held0 = ctx["snap0"]["counters"].get(params["gauge"])
    held1 = ctx["snap1"]["counters"].get(params["gauge"])
    return 100.0 * (held1 / held0 - 1.0) if held0 and held1 is not None else None
