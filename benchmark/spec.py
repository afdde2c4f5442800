"""Finds what BENCHMARK.json names.  A cell names a configuration and a
traffic mix; a configuration's file is given in BENCHMARK.json; a traffic
mix is ``benchmark/traffic/<name>.json`` and names its generator, a
module ``benchmark/generators/<generator>.py``; a per-layer metric is read
by ``benchmark/layer_metrics/<name>.py``.  Adding any of them is adding a
file and an entry: nothing here changes."""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(path: Optional[Path] = None) -> Dict[str, Any]:
    return json.loads((path or REPO / "BENCHMARK.json").read_text())


def _one(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _one(bench["workloads"], name, "workload")


def config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    entry = _one(bench["configs"], name, "config")
    return json.loads((REPO / entry["file"]).read_text())


def traffic(name: str) -> Dict[str, Any]:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def generator(name: str):
    return importlib.import_module(f"benchmark.generators.{name}")


def layer_reader(name: str):
    """The module whose ``read(ctx)`` gives per-layer metric ``name``:
    ``layer_metrics/<name>.py``, or for a quantity split by cell
    (``<base>.<cell>``, e.g. ``device_idle_pct.sat``) the shared
    ``layer_metrics/<base>.py``."""
    path = HERE / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "layer_metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark.layer_metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict[str, Any], cell_name: str) -> bool:
    """Does ``metric`` belong in ``cell_name``'s result line?  Its
    ``workloads`` key lists its cells (every per-layer metric has one); an
    end-to-end metric without one (``setup_s``) is in every cell."""
    return cell_name in metric.get("workloads", [cell_name])


def peaks(kind: str) -> Dict[str, Any]:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]
