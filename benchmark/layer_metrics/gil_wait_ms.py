"""Mean wait of a runnable Python thread for the interpreter, per wake of
the probe (``obs/trace.install_gil_probe``): the mean duration of the
``crdt.probe.gil`` spans, in ms, less the probe's nominal 1 ms sleep;
None when no probe span started in the window.  A probe span closes only
once its thread holds the GIL again, so what it lasts beyond the sleep
is GIL contention or a stall of the whole host.  On an idle TPU v5e
host the probe alone reads 0.146 ms (p50 0.121 ms, p99 1.09 ms over 447
wakes): the sleep's own overshoot, kept in the reading, not subtracted.
Beside one CPU-bound Python thread it reads 6.09 ms, the 5 ms switch
interval plus that overshoot.  The mean counts every wake, so a stall
of the whole host weighs in it: one 4 s stall in a 51 s window of some
5,000 wakes adds ~0.8 ms.  ``benchmark/span_split.py`` gives the mean
without the wakes that a witness process saw stall, and the median.
Serves every ``gil_wait_ms.<cell>`` entry of BENCHMARK.json."""

PROBE_SPAN = "crdt.probe.gil"
SLEEP_MS = 1.0


def read(ctx):
    secs, n = ctx["trace"]["spans"].get(PROBE_SPAN, (0.0, 0))
    return secs / n * 1e3 - SLEEP_MS if n else None
