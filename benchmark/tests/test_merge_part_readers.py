"""The readers of the merge's parts and of the interpreter-wait probe, on
a hand-made reduced trace: a per-second sum over the window of a part's
spans (None when the window holds none, as on a program without the
span), and the probe's interpreter wait past its nominal sleep (None
with no probe span)."""
from __future__ import annotations

import pytest

from benchmark import spec

PART_SUMS = {
    "device_wait_ms_per_s.sat": "crdt.wait.device",
    "device_wait_ms_per_s.ref": "crdt.wait.device",
    "accept_ms_per_s.sat": "crdt.accept",
    "pack_ms_per_s.sat": "crdt.pack",
}
GIL = ["gil_wait_ms.sat", "gil_wait_ms.ref"]


def ctx(spans, window_s=50.0):
    return {"trace": {"spans": spans, "window_s": window_s}}


@pytest.mark.parametrize("name", sorted(PART_SUMS))
def test_merge_part_readers(name):
    read = spec.layer_reader(name).read
    assert read(ctx({})) is None
    assert read(ctx({"crdt.merge": (9.0, 30)})) is None
    got = read(ctx({PART_SUMS[name]: (2.5, 450), "crdt.merge": (9.0, 30)}))
    assert got == pytest.approx(50.0)
    assert read(ctx({PART_SUMS[name]: (2.5, 450)}, window_s=0.0)) is None


@pytest.mark.parametrize("name", GIL)
def test_gil_wait_readers(name):
    """The probe span's mean in ms, less the nominal 1 ms sleep."""
    read = spec.layer_reader(name).read
    assert read(ctx({})) is None
    assert read(ctx({"crdt.merge": (1.0, 4)})) is None
    # 5,000 wakes of 1.25 ms each: a quarter of a ms past the sleep
    assert read(ctx({"crdt.probe.gil": (6.25, 5000)})) == pytest.approx(0.25)
    # wakes that slept exactly their 1 ms waited for nothing
    assert read(ctx({"crdt.probe.gil": (5.0, 5000)})) == pytest.approx(0.0)


def test_every_part_reader_is_declared():
    """Each reader above has its entry in BENCHMARK.json, read from the
    program's spans, in the cell its name gives."""
    cells = {"sat": "ycsb_ks.load_sat", "ref": "ref_kv.mixed_rate"}
    entries = {m["name"]: m for m in spec.load()["per_layer"]}
    for name in [*PART_SUMS, *GIL]:
        m = entries[name]
        assert m["source"] == "program_span"
        assert m["workloads"] == [cells[name.rsplit(".", 1)[1]]]
