"""BENCHMARK.json keeps to the contract's shape, and the layout is driven
by data: a configuration, a traffic mix or a per-layer metric dropped
into its directory is found by name, with no existing file edited."""
from __future__ import annotations

import hashlib
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec

BENCH = spec.load()
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024
    for p in BENCH["paths"]:
        assert (spec.REPO / p).is_dir()
    assert BENCH["command"][1] == "benchmark/run.py"


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_names_and_units(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        assert set(e) - {"workloads"} == KEYS[section], e["name"]
        assert spec.NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert spec.UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
        for k in e.get("reduced", []):
            assert spec.NAME.match(k)


def test_cells_and_metrics_resolve():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {c["name"] for c in BENCH["workloads"]}
    for c in BENCH["workloads"]:
        assert c["chips"] in (1, 4)
        cfg = spec.config(BENCH, c["config"])
        assert cfg["name"] == c["config"]
        gen = spec.generator(spec.traffic(c["traffic"])["generator"])
        for fn in ("plan", "preload", "warm", "drive", "judge"):
            assert callable(getattr(gen, fn))
        reported = [m for m in BENCH["end_to_end"] if spec.applies(m, c["name"])]
        assert len(reported) >= 2
        assert any(spec.applies(m, c["name"]) for m in BENCH["per_layer"])
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        # a per-layer metric names its cells, each of which reports the
        # end-to-end metric it moves
        assert m["workloads"], m["name"]
        for w in m["workloads"]:
            assert spec.applies(e2e[m["moves"]], w), (m["name"], w)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["workloads"]) <= cells
        reader = spec.layer_reader(m["name"])
        empty = {"counters": {"ops_ingested": 0, "merge_dispatches": 0},
                 "trace": {"programs": {}, "spans": {}, "window_s": 0.0,
                           "busy_s": 0.0},
                 "peaks": spec.peaks("TPU v5 lite"), "seconds": 1.0}
        assert reader.read(empty) is None   # nothing to read: nothing said
        layers.setdefault(m["layer"], []).append(m["name"])
    for c in BENCH["configs"]:
        path = spec.REPO / c["file"]
        assert path.is_file() and str(path).startswith(str(spec.HERE))
        assert json.loads(path.read_text())["reduced"] == c["reduced"]
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def _digest(root: Path):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """Copy the benchmark, add one of each kind of file, and find each by
    name through the unchanged harness; nothing that was there changed."""
    here = tmp_path / "benchmark"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(here)
    (here / "configs" / "probe_cfg.json").write_text(json.dumps(
        {"name": "probe_cfg", "deployment": {"replicas": 2, "cluster": {}},
         "reduced": []}))
    (here / "traffic" / "probe_mix.json").write_text(json.dumps(
        {"generator": "kv_mixed", "write_rate_per_s": 1}))
    (here / "layer_metrics" / "probe_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    after = _digest(here)
    assert {k: v for k, v in after.items() if k in before} == before
    monkeypatch.setattr(spec, "HERE", here)
    monkeypatch.setattr(spec, "REPO", tmp_path)
    bench = dict(BENCH, configs=BENCH["configs"] + [
        {"name": "probe_cfg", "source": "x", "file": "benchmark/configs/probe_cfg.json",
         "reduced": [], "why": "x"}])
    assert spec.config(bench, "probe_cfg")["deployment"]["replicas"] == 2
    assert spec.traffic("probe_mix")["write_rate_per_s"] == 1
    assert spec.layer_reader("probe_metric.x").read({}) == 42.0
    metric = {"name": "probe_metric.x", "moves": "write_p95_ms",
              "workloads": ["ref_kv.mixed_rate"]}
    assert spec.applies(metric, "ref_kv.mixed_rate")
    assert not spec.applies(metric, "ycsb_ks.load_sat")
    # a quantity split by cell, <base>.<cell>, is read by a shared <base>.py
    (here / "layer_metrics" / "probe_shared.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    assert spec.layer_reader("probe_shared.new_cell").read({}) == 7.0
