"""Share of the traced window in which no operation ran on the chip:
100 * (1 - union of device op intervals / window).  Serves every
``device_idle_pct.<cell>`` entry of BENCHMARK.json (the quantity is split
by the end-to-end metric it moves in each cell)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
