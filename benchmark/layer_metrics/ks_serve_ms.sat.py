"""Mean duration of the ``crdt.http.get_ks_gossip`` span: one GET
/ks/gossip serve (one shard's delta payload, audit snapshot under the
shard lock, response), from the trace.  Moves write_ops_per_s."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.http.get_ks_gossip", (0.0, 0))
    return secs / n * 1e3 if n else None
