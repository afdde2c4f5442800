"""Milliseconds per second of window that lock holders spent blocked on
the device: the sum of the ``crdt.wait.device`` spans (the host sync of
each merge's result, which waits for that merge and any queued ahead of
it on the chip) over the traced window; None when no merge synced in it.
Serves every ``device_wait_ms_per_s.<cell>`` entry of BENCHMARK.json."""


def read(ctx):
    tr = ctx["trace"]
    secs, n = tr["spans"].get("crdt.wait.device", (0.0, 0))
    if not tr["window_s"] or not n:
        return None
    return secs * 1e3 / tr["window_s"]
