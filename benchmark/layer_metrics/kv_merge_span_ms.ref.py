"""Mean duration of the ``crdt.merge`` host span (ReplicaNode.receive:
decode, accept, pack and the merge dispatch of one gossip payload), from
the trace.  Moves visible_p95_ms."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.merge", (0.0, 0))
    return secs / n * 1e3 if n else None
