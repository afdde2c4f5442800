"""Milliseconds per second of window spent waiting for a replica's node
lock: the sum of the ``crdt.wait.node_lock`` spans (opened only when an
acquisition has to block) over the traced window; 0.0 when no
acquisition blocked.  Serves every ``lock_wait_ms_per_s.<cell>`` entry of
BENCHMARK.json."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"]:
        return None
    secs, _ = tr["spans"].get("crdt.wait.node_lock", (0.0, 0))
    return secs * 1e3 / tr["window_s"]
