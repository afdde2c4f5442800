"""Milliseconds per second of window spent on the audit plane's upkeep:
the sum of every ``crdt.audit.*`` span (digest upkeep per accepted batch,
frontier clamps, watchdog ticks) over the traced window; 0.0 when none
ran.  Moves write_ops_per_s."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"]:
        return None
    secs = sum(s for name, (s, _) in tr["spans"].items()
               if name.startswith("crdt.audit."))
    return secs * 1e3 / tr["window_s"]
