"""The trace reduction: busy union, idle gaps and their host-span labels,
time per program and per span, on hand-made intervals and on a small
trace recorded on a v5e."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmark import trace

DATA = Path(__file__).parent / "data"


def synthetic():
    ms = 1_000_000
    return {
        "devices": 1,
        # two merges (one with two overlapping ops) and a rebuild
        "ops": [(10 * ms, 14 * ms, "jit__merge_checked", 0),
                (12 * ms, 16 * ms, "jit__merge_checked", 0),
                (40 * ms, 45 * ms, "jit__merge_checked", 0),
                (70 * ms, 72 * ms, "jit_rebuild", 0),
                (150 * ms, 160 * ms, "jit__merge_checked", 0)],  # outside
        "programs": [(10 * ms, 16 * ms, "jit__merge_checked"),
                     (40 * ms, 45 * ms, "jit__merge_checked"),
                     (70 * ms, 72 * ms, "jit_rebuild"),
                     (150 * ms, 160 * ms, "jit__merge_checked")],
        "spans": [(0, 100 * ms, trace.WINDOW),
                  (8 * ms, 17 * ms, "crdt.merge"),
                  (20 * ms, 38 * ms, "crdt.ks_pull"),
                  (22 * ms, 30 * ms, "crdt.merge"),
                  (150 * ms, 160 * ms, "crdt.merge")],
    }


def test_reduce_synthetic():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.006 + 0.005 + 0.002)
    assert r["programs"]["jit__merge_checked"] == pytest.approx((0.011, 2))
    assert r["programs"]["jit_rebuild"] == pytest.approx((0.002, 1))
    assert r["spans"]["crdt.merge"] == pytest.approx((0.017, 2))
    assert trace.program_time(r, "merge_checked") == pytest.approx((0.011, 2))
    gaps = r["idle_gaps"]
    # idle: 72..100 and 45..70 ms under no span, 16..40 ms mostly under
    # crdt.ks_pull, 0..10 ms partly under crdt.merge; longest first
    assert [g[1] for g in gaps] == pytest.approx([0.028, 0.025, 0.024, 0.010])
    assert [g[0] for g in gaps] == ["none", "none", "crdt.ks_pull", "crdt.merge"]
    assert sum(s for _, s in gaps) == pytest.approx(0.1 - r["busy_s"])


def test_union_and_gaps():
    assert trace.union([(5, 9), (1, 3), (2, 4), (8, 12)], 0, 10) == [(1, 4), (5, 10)]
    assert trace.gaps([(1, 4), (5, 10)], 0, 10) == [(0, 1), (4, 5)]
    assert trace.union([(0, 10, "a"), (5, 20, "b")], 0, 15) == [(0, 15)]


def test_window_must_be_marked():
    t = synthetic()
    t["spans"] = [s for s in t["spans"] if s[2] != trace.WINDOW]
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_names():
    assert trace.program_name("jit__merge_checked(42)") == "jit__merge_checked"
    assert trace.span_name("crdt.join.merge#trace=ab-1") == "crdt.join.merge"


def test_recorded_v5e_trace():
    """A trace recorded on one v5e (.bench_scratch probe, PR 22): three
    merges under crdt.merge spans and one rebuild inside a bench.window
    span.  extract() + reduce() must give what they gave on the chip
    (v5e_probe.json, written there by the same code)."""
    tr = trace.extract(str(DATA / "v5e_probe.xplane.pb"))
    want = json.loads((DATA / "v5e_probe.json").read_text())
    assert tr["devices"] == 1
    r = trace.reduce(tr)
    assert {k: list(v) for k, v in r["programs"].items()} == want["programs"]
    assert r["busy_s"] == want["busy_s"]
    assert r["window_s"] == want["window_s"]
    assert r["spans"]["crdt.merge"][1] == 3
    assert r["programs"]["jit__merge_checked"][1] == 3
    assert r["programs"]["jit_rebuild"][1] == 1
    # device ops lie inside their programs: busy is at most their sum
    assert 0 < r["busy_s"] <= sum(s for s, _ in r["programs"].values()) + 1e-9
    assert r["busy_s"] < r["window_s"]
