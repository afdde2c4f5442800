"""The readers of the program's host spans, on a hand-made reduced trace:
a mean over the spans that started in the window (None when none did), a
per-second sum or share over the window (0.0 when none ran)."""
from __future__ import annotations

import pytest

from benchmark import spec

MEANS = {
    "http_write_ms.ref": "crdt.http.post_data",
    "http_page_ms.sat": "crdt.http.post_ingest_page",
    "admit_wait_ms.ref": "crdt.wait.admit",
    "gossip_serve_ms.ref": "crdt.http.get_gossip",
    "ks_serve_ms.sat": "crdt.http.get_ks_gossip",
    "agent_round_ms.ref": "crdt.agent.round",
}
SUMS = ["lock_wait_ms_per_s.sat", "lock_wait_ms_per_s.ref",
        "audit_ms_per_s.sat", "py_gc_pct.sat", "py_gc_pct.ref"]


def ctx(spans, window_s=50.0):
    return {"trace": {"spans": spans, "window_s": window_s}}


@pytest.mark.parametrize("name", sorted(MEANS))
def test_mean_span_readers(name):
    read = spec.layer_reader(name).read
    assert read(ctx({})) is None
    assert read(ctx({"crdt.merge": (1.0, 4)})) is None
    got = read(ctx({MEANS[name]: (0.5, 4), "crdt.merge": (9.0, 1)}))
    assert got == pytest.approx(125.0)


def test_sum_span_readers():
    spans = {"crdt.wait.node_lock": (2.0, 300), "crdt.py_gc": (1.5, 12),
             "crdt.audit.observe": (0.25, 900),
             "crdt.audit.digest_at": (0.5, 40),
             "crdt.audit.evaluate": (0.25, 5), "crdt.merge": (9.0, 30)}
    want = {"lock_wait_ms_per_s": 40.0, "audit_ms_per_s": 20.0,
            "py_gc_pct": 3.0}
    for name in SUMS:
        read = spec.layer_reader(name).read
        assert read(ctx({})) == 0.0
        assert read(ctx(spans)) == pytest.approx(want[name.split(".")[0]])


def test_every_span_reader_is_declared():
    """Each reader above has its entry in BENCHMARK.json, read from the
    program's spans, in the cell its name gives."""
    cells = {"sat": "ycsb_ks.load_sat", "ref": "ref_kv.mixed_rate"}
    entries = {m["name"]: m for m in spec.load()["per_layer"]}
    for name in [*MEANS, *SUMS]:
        m = entries[name]
        assert m["source"] == "program_span"
        assert m["workloads"] == [cells[name.rsplit(".", 1)[1]]]
