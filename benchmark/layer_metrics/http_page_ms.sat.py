"""Mean duration of the ``crdt.http.post_ingest_page`` span: one whole
POST /ingest/page handler (page decode, fan-out to the shard lanes, the
admission waits, acknowledgement), from the trace.  Moves
write_ops_per_s."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.http.post_ingest_page", (0.0, 0))
    return secs / n * 1e3 if n else None
