"""Share of the traced window spent in Python's garbage collector: 100 x
the sum of the ``crdt.py_gc`` spans (one per generation-1 or -2
collection) over the window; 0.0 when none ran.  Serves every
``py_gc_pct.<cell>`` entry of BENCHMARK.json."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"]:
        return None
    secs, _ = tr["spans"].get("crdt.py_gc", (0.0, 0))
    return 100.0 * secs / tr["window_s"]
