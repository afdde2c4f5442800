"""Ops per merge dispatch: window deltas of the ``ops_ingested`` and
``merge_dispatches`` counters summed over replicas (the page door and
admission fuse writes into drains; gossip receives fold whole deltas).
Serves every ``ops_per_dispatch.<cell>`` entry of BENCHMARK.json."""


def read(ctx):
    c = ctx["counters"]
    return c["ops_ingested"] / c["merge_dispatches"] if c["merge_dispatches"] else None
