"""Split the served path's long spans into their parts, thread by thread,
for one traced run of a cell: the tables of PERF.md §5.

    python3 benchmark/span_split.py --workload <cell> --seed <n> \\
        --seconds <s> --out <file.json>

It runs the cell as ``benchmark/run.py --trace 1`` does (the same
``run``), and besides:

* keeps the trace's host spans WITH their thread (``benchmark/trace.py``
  drops it) and, for every ``crdt.merge``, ``crdt.drain`` and
  ``crdt.http.post_ingest_page`` that starts in the window, gives each
  instant of it to the innermost ``crdt.*`` span on the same thread that
  covers it (``self_ms``; the parent's own share is its remainder) and
  sums every nested span whole (``inclusive_ms``);
* times each call of the node's accept and pack steps and of
  ``receive`` in the window on two clocks: the wall and the calling
  thread's CPU time (``time.thread_time_ns``).  What the wall holds past
  the CPU time the thread spent waiting: for the interpreter lock, for
  a core, or in a stall of the host.  Rows in and out come from the
  calls' own arguments and results, not from an estimate;
* replays the largest receive of the window, uncontended, on fresh nodes
  built like the receiving shard after the run (``replay``);
* runs a witness beside the server: a process that imports nothing of
  this repo and logs each of its 5 ms sleeps that wakes 10 ms or more
  late, on the system's monotonic clock.  ``probe`` gives the
  ``crdt.probe.gil`` waits with and without the wakes that overlap a
  witness stall.

No result line is printed; the cell's result object, with the split
under ``"split"``, goes to ``--out`` and its summary to standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import subprocess
import sys
import threading
import time
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PARENTS = ("crdt.merge", "crdt.drain", "crdt.http.post_ingest_page")
PROBE_SPAN = "crdt.probe.gil"
PROBE_SLEEP_MS = 1.0
WITNESS_SLEEP_S = 0.005
WITNESS_LATE_MS = 10.0
# the witness: no import of this repo, one line per late wake
WITNESS = f"""
import sys, time
while True:
    t = time.monotonic_ns()
    time.sleep({WITNESS_SLEEP_S})
    late = (time.monotonic_ns() - t) / 1e6 - {WITNESS_SLEEP_S * 1e3}
    if late >= {WITNESS_LATE_MS}:
        print(time.monotonic_ns(), round(late, 3), flush=True)
"""

Span = Tuple[int, int, str]  # (start_ns, end_ns, name)


def thread_spans(path: str) -> List[List[Span]]:
    """The ``crdt.*`` and ``bench.window`` spans of each host thread of
    one xplane file, each thread's list sorted by start."""
    from jax.profiler import ProfileData

    from benchmark.trace import WINDOW, span_name

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
                      span_name(e.name)) for e in line.events
                     if e.name.startswith(("crdt.", WINDOW))]
            if spans:
                out.append(sorted(spans, key=lambda s: (s[0], -s[1])))
    return out


def split(threads: List[List[Span]], lo: int, hi: int,
          parents=PARENTS) -> Dict[str, dict]:
    """For each parent name: how many start in [lo, hi), their mean wall
    in ms, and per nested span name its mean ``self_ms`` (the instants it
    is the innermost span) and ``inclusive_ms``.  The parent's own
    ``self_ms`` is what no nested span covers; the self times of one
    parent sum to its wall."""
    acc: Dict[str, dict] = {}
    for spans in threads:
        for i, (ps, pe, pname) in enumerate(spans):
            if pname not in parents or not lo <= ps < hi:
                continue
            inner = []
            for s in spans[i + 1:]:
                if s[0] >= pe:
                    break
                if s[1] <= pe:
                    inner.append(s)
            a = acc.setdefault(pname, {"n": 0, "wall": 0, "self": {},
                                       "inclusive": {}})
            a["n"] += 1
            a["wall"] += pe - ps
            selfs = {pname: pe - ps}
            stack = [(ps, pe, pname)]
            for s, e, name in inner:
                while stack[-1][1] <= s:
                    stack.pop()
                # time given to this span is taken from its encloser
                selfs[stack[-1][2]] -= e - s
                selfs[name] = selfs.get(name, 0) + (e - s)
                a["inclusive"][name] = a["inclusive"].get(name, 0) + (e - s)
                stack.append((s, e, name))
            for name, ns in selfs.items():
                a["self"][name] = a["self"].get(name, 0) + ns
    out = {}
    for pname, a in acc.items():
        n, wall = a["n"], a["wall"]
        out[pname] = {
            "n": n, "wall_ms": wall / n / 1e6,
            "self_ms": {k: v / n / 1e6 for k, v in sorted(a["self"].items())},
            "self_share": {k: v / wall for k, v in sorted(a["self"].items())},
            "inclusive_ms": {k: v / n / 1e6
                             for k, v in sorted(a["inclusive"].items())},
        }
    return out


def probe(threads: List[List[Span]], lo: int, hi: int,
          stalls: List[Tuple[int, int]]) -> dict:
    """The probe's waits past its sleep (ms) for the wakes that start in
    [lo, hi): all of them, and those that overlap no witness stall
    (``stalls``: [start_ns, end_ns) on the trace's clock)."""
    waits, clear = [], []
    for spans in threads:
        for s, e, name in spans:
            if name != PROBE_SPAN or not lo <= s < hi:
                continue
            w = (e - s) / 1e6 - PROBE_SLEEP_MS
            waits.append(w)
            if not any(a < e and s < b for a, b in stalls):
                clear.append(w)

    def stats(xs):
        if not xs:
            return None
        q = statistics.quantiles(xs, n=10) if len(xs) > 1 else [xs[0]] * 9
        return {"n": len(xs), "mean_ms": statistics.fmean(xs),
                "p50_ms": statistics.median(xs), "p90_ms": q[8],
                "max_ms": max(xs),
                "over_5ms": sum(x > 5.0 for x in xs) / len(xs)}

    return {"all": stats(waits), "clear_of_stalls": stats(clear)}


def witness_stalls(lines: List[str], mono_to_trace) -> List[Tuple[int, int, float]]:
    """(start_ns, end_ns, late_ms) on the trace's clock of each late wake
    the witness logged: the whole sleep that overran."""
    out = []
    for line in lines:
        wake, late = line.split()
        end = mono_to_trace(int(wake))
        out.append((end - int((float(late) + WITNESS_SLEEP_S * 1e3) * 1e6),
                    end, float(late)))
    return out


class Calls:
    """Wall and thread-CPU time of each call of a few ReplicaNode methods
    made while the window is open."""

    METHODS = ("receive", "_accept_locked", "_pack_accepted_locked",
               "_pack_local_batch")

    def __init__(self):
        self.open = False
        self.rows: Dict[str, List[Tuple[int, int, int, int]]] = {
            m: [] for m in self.METHODS}
        self.largest = None  # (rows, node) of the largest accepted receive
        self._lock = threading.Lock()

    def install(self):
        from crdt_tpu.api.node import ReplicaNode

        for m in self.METHODS:
            setattr(ReplicaNode, m, self._wrap(m, getattr(ReplicaNode, m)))

    def _wrap(self, m, fn):
        def timed(node, *args, **kw):
            if not self.open:
                return fn(node, *args, **kw)
            w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
            out = fn(node, *args, **kw)
            w1, c1 = time.perf_counter_ns(), time.thread_time_ns()
            rows_in = len(args[0]) if m != "receive" and args[0] else 0
            rows_out = (out if isinstance(out, int) else
                        out[1] if isinstance(out, tuple) else len(out))
            with self._lock:
                self.rows[m].append((w1 - w0, c1 - c0, rows_in, rows_out))
                if m == "_accept_locked" and (
                        self.largest is None
                        or rows_in > len(self.largest[0])):
                    self.largest = (list(args[0]), node)
            return out
        return timed

    def summary(self) -> Dict[str, dict]:
        out = {}
        for m, xs in self.rows.items():
            if xs:
                n = len(xs)
                wall, cpu = sum(x[0] for x in xs), sum(x[1] for x in xs)
                out[m] = {"n": n, "wall_ms": wall / n / 1e6,
                          "cpu_ms": cpu / n / 1e6,
                          "cpu_share": cpu / wall if wall else None,
                          "rows_in": sum(x[2] for x in xs) / n,
                          "rows_out": sum(x[3] for x in xs) / n}
        return out


def replay(rows, like, times: int = 3) -> List[dict]:
    """Accept and pack ``rows`` on ``times`` fresh nodes built like
    ``like`` (its rid, capacity and audit), alone: wall and CPU ms."""
    from crdt_tpu.api.node import ReplicaNode

    out = []
    for i in range(times + 1):
        node = ReplicaNode(rid=like.rid, capacity=like.log.capacity,
                           clock=like.clock)
        if like.digest is not None and like.digest.enabled:
            node.enable_audit(plane="replay")
        with node._lock:
            w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
            accepted = node._accept_locked(rows)
            w1, c1 = time.perf_counter_ns(), time.thread_time_ns()
            _, fresh = node._pack_accepted_locked(accepted)
            w2, c2 = time.perf_counter_ns(), time.thread_time_ns()
        if i == 0:
            continue  # the first also pays this process's first-use costs
        out.append({"rows": len(rows), "accepted": len(accepted),
                    "fresh": fresh,
                    "accept_wall_ms": (w1 - w0) / 1e6,
                    "accept_cpu_ms": (c1 - c0) / 1e6,
                    "pack_wall_ms": (w2 - w1) / 1e6,
                    "pack_cpu_ms": (c2 - c1) / 1e6})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    metavar="PART.KEY=JSON")
    args = ap.parse_args(argv)
    from benchmark import run as br
    from benchmark import spec
    from benchmark import trace as tracelib

    if args.rehearse_cpu:
        # the CPU has no published peaks: a rehearsal reads the first
        # device's, and its roofline shares mean nothing
        known = spec.peaks
        first = next(iter(json.loads(
            (spec.HERE / "peaks.json").read_text())["devices"]))
        spec.peaks = lambda kind: known(first)

    scale = {}
    for item in args.set:
        key, _, value = item.partition("=")
        part, _, rest = key.partition(".")
        scale[(part, rest)] = json.loads(value)

    calls = Calls()
    calls.install()
    marks: Dict[str, object] = {}
    window_span = br.window_span

    @contextlib.contextmanager
    def marked_window(trace):
        with window_span(trace):
            marks["open_mono_ns"] = time.monotonic_ns()
            calls.open = True
            try:
                yield
            finally:
                calls.open = False

    extract = tracelib.extract

    def keep_threads(path):
        marks["threads"] = thread_spans(path)
        return extract(path)

    br.window_span = marked_window
    tracelib.extract = keep_threads
    wpath = args.out + ".witness"
    with open(wpath, "w") as wf:
        witness = subprocess.Popen([sys.executable, "-c", WITNESS], stdout=wf)
        try:
            result = br.run(args.workload, args.seed, args.seconds, True,
                            rehearse=args.rehearse_cpu, scale=scale)
        finally:
            witness.terminate()
            witness.wait()
    with open(wpath) as wf:
        wlines = [x for x in wf.read().splitlines() if x.strip()]

    threads = marks["threads"]
    lo, hi = next((s, e) for spans in threads for s, e, n in spans
                  if n == tracelib.WINDOW)

    def mono_to_trace(ns: int) -> int:
        return lo + ns - marks["open_mono_ns"]

    stalls = witness_stalls(wlines, mono_to_trace)
    in_window = [x for x in stalls if x[0] < hi and lo < x[1]]
    out = {
        "spans": split(threads, lo, hi),
        "calls": calls.summary(),
        "replay": (replay(*calls.largest) if calls.largest else []),
        "probe": probe(threads, lo, hi, [(s, e) for s, e, _ in stalls]),
        "witness": {"stalls_in_window": len(in_window),
                    "stall_ms_in_window": sum(x[2] for x in in_window),
                    "worst_ms": max((x[2] for x in in_window), default=0.0)},
    }
    result["split"] = out
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1, default=str)
    print(json.dumps({"correct": result["correct"],
                      "metrics": result["metrics"], "split": out},
                     default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
