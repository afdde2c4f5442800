"""Milliseconds per second of window spent accepting gossip rows: the sum
of the ``crdt.accept`` spans (a receive's per-row dedup, command map and
delta-index bookkeeping and its digest upkeep, under the shard lock) over
the traced window; None when no receive accepted rows in it.  Moves
write_ops_per_s."""


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr["spans"].get("crdt.accept", (0.0, 0))
    if not tr["window_s"] or not n:
        return None
    return secs * 1e3 / tr["window_s"]
