"""Milliseconds per second of window spent packing rows into merge
columns: the sum of the ``crdt.pack`` spans (a receive's accepted rows,
or a drain's locally minted batch with its bookkeeping and digest upkeep,
under the shard lock) over the traced window; None when nothing was
packed in it.  Moves write_ops_per_s."""


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr["spans"].get("crdt.pack", (0.0, 0))
    if not tr["window_s"] or not n:
        return None
    return secs * 1e3 / tr["window_s"]
