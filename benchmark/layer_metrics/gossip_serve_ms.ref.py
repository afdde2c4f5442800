"""Mean duration of the ``crdt.http.get_gossip`` span: one GET /gossip
serve (delta payload, audit snapshot under the node lock, response), from
the trace.  Moves visible_p95_ms."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.http.get_gossip", (0.0, 0))
    return secs / n * 1e3 if n else None
