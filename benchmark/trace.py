"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``extract`` turns a ``.xplane.pb`` into plain interval lists; everything
after it is pure arithmetic over those lists, so every PR computes busy
time, program time, span time and idle gaps the same way, and
benchmark/tests checks it on a small recorded trace.

* device ops: the intervals in which an operation ran on a chip (the
  ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), each tagged with
  the HLO module (program) it belongs to;
* programs: one interval per program execution (the ``XLA Modules``
  line), named by module with its ``(<id>)`` suffix dropped;
* host spans: the program's ``crdt.*`` profiler annotations
  (``obs/trace.span``, ``obs/devtime.dispatch_annotation``), with any
  ``#trace=<id>`` suffix dropped.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)
Named = Tuple[int, int, str]        # (start_ns, end_ns, name)
DeviceOp = Tuple[int, int, str, int]  # (start_ns, end_ns, program, chip)

_SUFFIX = re.compile(r"\(\d+\)$")
#: the host span benchmark/run.py opens over the measured window
WINDOW = "bench.window"


def program_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def span_name(name: str) -> str:
    return name.split("#", 1)[0]


def extract(path: str) -> Dict[str, object]:
    """{"ops": [DeviceOp], "programs": [Named], "spans": [Named],
    "devices": n} from one xplane file; op and program names are the
    module's."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Named] = []
    programs: List[Named] = []
    spans: List[Named] = []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = devices
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        mod = dict(e.stats).get("hlo_module", "")
                        s = int(e.start_ns)
                        ops.append((s, s + int(e.duration_ns),
                                    program_name(str(mod)), chip))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        s = int(e.start_ns)
                        programs.append((s, s + int(e.duration_ns),
                                         program_name(e.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("crdt.", WINDOW)):
                        s = int(e.start_ns)
                        spans.append((s, s + int(e.duration_ns),
                                      span_name(e.name)))
    return {"ops": ops, "programs": programs, "spans": spans,
            "devices": devices}


def union(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) between the busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def per_program(programs: Iterable[Named], lo: int, hi: int
                ) -> Dict[str, Tuple[int, int]]:
    """{name: (total ns, count)} of the intervals (program executions or
    host spans) that start in [lo, hi)."""
    out: Dict[str, List[int]] = {}
    for s, e, name in programs:
        if lo <= s < hi:
            acc = out.setdefault(name, [0, 0])
            acc[0] += e - s
            acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def label(gap: Interval, spans: List[Named]) -> str:
    """The host span that overlaps ``gap`` the most (the innermost, i.e.
    shortest, on a tie), or "none"."""
    best: Optional[Tuple[int, int, str]] = None
    for s, e, name in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (ov, -(e - s), name)
        if best is None or key > best:
            best = key
    return best[2] if best else "none"


def reduce(tr: Dict[str, object], top: int = 10) -> Dict[str, object]:
    """Everything the layer readers and the breakdown need, over the
    window that the ``bench.window`` span marks."""
    marks = [(s, e) for s, e, n in tr["spans"] if n == WINDOW]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} {WINDOW} spans in the trace")
    lo, hi = marks[0]
    devices = max(1, int(tr["devices"]))
    ops = tr["ops"] or [(s, e, n, 0) for s, e, n in tr["programs"]]
    per_chip = [union([o for o in ops if o[3] == d], lo, hi)
                for d in range(devices)]
    busy = sum(e - s for b in per_chip for s, e in b) / devices
    b = per_chip[0]  # idle gaps are read on the first chip
    progs = per_program(tr["programs"], lo, hi)
    spans = [s for s in tr["spans"]
             if s[1] > lo and s[0] < hi and s[2] != WINDOW]
    idle = sorted(gaps(b, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "programs": {k: (ns / 1e9, n) for k, (ns, n) in progs.items()},
        "spans": {k: (ns / 1e9, n)
                  for k, (ns, n) in per_program(spans, lo, hi).items()},
        "device_ops": [[k, ns / 1e9] for k, (ns, _) in
                       sorted(progs.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps": [[label(g, spans), (g[1] - g[0]) / 1e9] for g in idle],
    }


def program_time(tr: Dict[str, object], needle: str) -> Tuple[float, int]:
    """(device seconds, executions) of every program whose name contains
    ``needle``."""
    secs, n = 0.0, 0
    for name, (s, k) in tr["programs"].items():
        if needle in name:
            secs += s
            n += k
    return secs, n
