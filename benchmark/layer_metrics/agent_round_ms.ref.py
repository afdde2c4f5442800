"""Mean duration of the ``crdt.agent.round`` span: one gossip agent
round's work (every lattice's pull plus the cadenced compaction, GC and
watchdog ticks; not the period wait), from the trace.  Moves
visible_p95_ms."""


def read(ctx):
    secs, n = ctx["trace"]["spans"].get("crdt.agent.round", (0.0, 0))
    return secs / n * 1e3 if n else None
