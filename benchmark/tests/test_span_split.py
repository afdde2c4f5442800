"""The per-thread split of PERF.md §5 (benchmark/span_split.py), on
hand-made spans: each instant of a parent goes to the innermost span on
its thread, the self times of one parent sum to its wall, other threads
and spans outside the window count for nothing, and the probe's waits
leave out the wakes that overlap a witness stall."""
from __future__ import annotations

import pytest

from benchmark import span_split

MS = 1_000_000


def test_split_gives_each_instant_to_the_innermost_span():
    receive = [(0, 100 * MS, "crdt.merge"),
               (5 * MS, 65 * MS, "crdt.accept"),
               (50 * MS, 60 * MS, "crdt.audit.observe"),
               (65 * MS, 80 * MS, "crdt.pack"),
               (80 * MS, 82 * MS, "crdt.join.merge"),
               (82 * MS, 98 * MS, "crdt.wait.device")]
    # a second receive on the same thread, and one that starts after the
    # window: only the first two count
    later = [(s + 200 * MS, e + 200 * MS, n) for s, e, n in receive]
    outside = [(s + 900 * MS, e + 900 * MS, n) for s, e, n in receive]
    other_thread = [(10 * MS, 90 * MS, "crdt.probe.gil")]
    got = span_split.split([receive + later + outside, other_thread],
                           0, 500 * MS)
    assert set(got) == {"crdt.merge"}
    m = got["crdt.merge"]
    assert m["n"] == 2 and m["wall_ms"] == pytest.approx(100.0)
    assert m["self_ms"] == pytest.approx({
        "crdt.merge": 7.0, "crdt.accept": 50.0, "crdt.audit.observe": 10.0,
        "crdt.pack": 15.0, "crdt.join.merge": 2.0, "crdt.wait.device": 16.0})
    assert sum(m["self_share"].values()) == pytest.approx(1.0)
    assert m["inclusive_ms"]["crdt.accept"] == pytest.approx(60.0)


def test_probe_waits_with_and_without_stalls():
    wakes = [(i * 10 * MS, i * 10 * MS + int(w * MS), "crdt.probe.gil")
             for i, w in enumerate([1.5, 1.5, 1.5, 101.0])]
    # the last wake overlaps a witness stall; a wake before the window
    # counts for nothing
    wakes.append((-50 * MS, -30 * MS, "crdt.probe.gil"))
    got = span_split.probe([wakes], 0, 500 * MS, [(40 * MS, 120 * MS)])
    assert got["all"]["n"] == 4
    assert got["all"]["mean_ms"] == pytest.approx((0.5 * 3 + 100.0) / 4)
    assert got["clear_of_stalls"]["n"] == 3
    assert got["clear_of_stalls"]["mean_ms"] == pytest.approx(0.5)
    assert span_split.probe([[]], 0, 1, [])["all"] is None


def test_witness_stalls_on_the_trace_clock():
    # a wake 30 ms late at monotonic 1,000 ms; the window opened at
    # monotonic 900 ms, trace time 5,000 ms
    got = span_split.witness_stalls(
        [f"{1000 * MS} 30.0"], lambda ns: 5000 * MS + ns - 900 * MS)
    assert got == [(5100 * MS - 35 * MS, 5100 * MS, 30.0)]
