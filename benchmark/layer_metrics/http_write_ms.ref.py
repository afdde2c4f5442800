"""Mean duration of the ``crdt.http.post_data`` span: one whole POST
/data handler (body parse, admission wait, drain, acknowledgement), from
the trace.  Moves write_p95_ms."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.http.post_data", (0.0, 0))
    return secs / n * 1e3 if n else None
