"""Mean duration of the ``crdt.wait.admit`` span: an admission ticket's
wait from submission to its result (the flush deadline, the lane's drain
slot, any drain it runs itself), from the trace.  Moves write_p95_ms."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.wait.admit", (0.0, 0))
    return secs / n * 1e3 if n else None
