"""One run of one benchmark cell, as the driver calls it:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up boots the cell's NodeHosts in this process (the chip belongs to
one process), preloads the resident state, warms every shape the window
uses and starts the load generator (benchmark/loadgen.py, a process of
its own that never imports JAX).  The window runs ``--seconds`` with
every NodeHost's gossip agent running; then the writers stop, gossip
quiesces, and what the served read path returns on every replica is
compared with the plain reference (benchmark/oracle.py).

Earlier lines (``# ...``) carry set-up facts; the last line of standard
output is the result.  With ``--trace 1`` the window is traced and the
result carries the per-layer metrics instead of the end-to-end ones.

Builder-only options, never used by the driver and never printing a
result line: ``--rehearse-cpu`` (run on the CPU), ``--control
stale_read`` (the control of the correctness check: gossip stops at the
window's close and the replicas are read without quiescing), ``--set part.key=<json>`` (override a configuration or
traffic key, e.g. ``--set traffic.write_rate_per_s=300`` for the rate
sweep).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import spec  # noqa: E402


def say(*parts) -> None:
    print(*parts, flush=True)


class LoadClient:
    """The load-generator process of one run."""

    def __init__(self, generator: str, plan, urls, seconds: float):
        path = os.path.join(spec.HERE, "loadgen.py")
        self.proc = subprocess.Popen(
            [sys.executable, path], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self.proc.stdin.write(json.dumps({"generator": generator, "plan": plan,
                                          "urls": urls, "seconds": seconds})
                              + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline().strip()
        if line != "ready":
            raise RuntimeError(f"load generator did not start: {line!r}")

    def go(self, lead_s: float) -> float:
        """Start the window ``lead_s`` (plus a margin for the child to read
        the line) from now; returns the window's start."""
        t0 = time.monotonic() + 0.2 + lead_s
        self.proc.stdin.write(f"{t0!r}\n")
        self.proc.stdin.flush()
        return t0

    def result(self, timeout: float):
        out, _ = self.proc.communicate(timeout=timeout)
        if self.proc.returncode != 0:
            raise RuntimeError(f"load generator exited {self.proc.returncode}")
        return json.loads(out.strip().splitlines()[-1])

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)


def device_check(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        print(f"benchmark: JAX found no TPU (platform {devs[0].platform!r});"
              " refusing to run on a fallback", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), JAX sees "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(0.05, left))


def window_span(trace: bool):
    if not trace:
        import contextlib
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench.window")


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, control: str = "", bench=None,
        scale=None) -> dict:
    """One run; returns the result object (the last line's content) plus
    its ``facts``.  ``scale`` overrides configuration / traffic keys (the
    CPU tests shrink the cell through it)."""
    bench = bench or spec.load()
    cell = spec.cell(bench, workload)
    devs = device_check(int(cell["chips"]), rehearse)
    from crdt_tpu.utils import compile_cache

    from benchmark.cluster import Cluster
    from benchmark.compiles import CompileCounter

    say(f"# device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {compile_cache.enable()}")
    counter = CompileCounter()
    config = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    for (part, key), value in (scale or {}).items():
        target = {"config": config, "traffic": traffic}[part]
        *path, last = key.split(".")
        for k in path:
            target = target[k]
        target[last] = value
    gen = spec.generator(traffic["generator"])
    plan = gen.plan(config, traffic, seed)
    plan["control"] = control
    facts = {}
    cluster = client = None
    trace_dir = None
    try:
        t = time.monotonic()
        cluster = Cluster(config)
        facts["boot_s"] = time.monotonic() - t
        t = time.monotonic()
        facts.update(gen.preload(cluster, plan))
        facts["preload_s"] = time.monotonic() - t
        t = time.monotonic()
        mark = counter.mark()
        gen.warm(cluster, plan)
        cluster.gossip_round()
        _, comp, hits = counter.since(mark)
        facts["warm_s"] = time.monotonic() - t
        facts["warm_compiles"] = comp
        facts["warm_cache_hits"] = hits
        client = LoadClient(traffic["generator"], plan, cluster.urls, seconds)
        c0 = {k: cluster.counter(k) for k in ("merge_dispatches",
                                              "ops_ingested")}
        cluster.start_agents()
        if trace:
            import jax

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = counter.mark()
        t0 = client.go(gen.lead_s(plan))
        setup_s = t0 - T_START
        sleep_until(t0)
        # the traced run marks its window in the trace's own time base
        with window_span(trace):
            sleep_until(t0 + seconds)
        t_close = time.monotonic()
        if control == "stale_read":
            # the control: delivery stops at the close, so the reads that
            # follow miss what gossip had not yet carried
            cluster.stop_agents()
        c1 = {k: cluster.counter(k) for k in c0}
        if trace:
            jax.profiler.stop_trace()
        in_window = counter.since(mark)
        facts["window_traces"], facts["window_compiles"] = in_window[:2]
        facts["window_traced_programs"] = counter.names_since(mark)
        facts["close_lag_s"] = t_close - (t0 + seconds)
        peak = peak_bytes(devs)
        out = client.result(timeout=seconds + 300)
        t = time.monotonic()
        judged = gen.judge(cluster, plan, out, seconds, control)
        facts["check_s"] = time.monotonic() - t
        facts.update(judged["facts"])
        facts["log_rows_max"] = max(cluster.log_rows() or [0])
    finally:
        if cluster is not None:
            facts["gossip_loop_errors"] = cluster.stop()
        if client is not None:
            client.close()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    counters = {k: c1[k] - c0[k] for k in c0}
    result = {"correct": all(v <= lim for v, lim in judged["checks"].values())
              and not facts["gossip_loop_errors"],
              "attempted": judged["attempted"], "failed": judged["failed"]}
    facts["setup_s"] = setup_s
    facts["counters"] = counters
    if trace:
        from benchmark import trace as tracelib

        t = time.monotonic()
        files = [os.path.join(r, f) for r, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        tr = tracelib.reduce(tracelib.extract(files[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        facts["trace_reduce_s"] = time.monotonic() - t
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        ctx = {"counters": counters, "trace": tr, "seconds": seconds,
               "peaks": spec.peaks(d.device_kind), "cell": workload}
        metrics = {}
        for m in bench["per_layer"]:
            if not spec.applies(m, workload):
                continue
            v = spec.layer_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    else:
        judged["metrics"]["setup_s"] = setup_s
        metrics = {}
        for m in bench["end_to_end"]:
            if spec.applies(m, workload):
                metrics[m["name"]] = {"value": judged["metrics"][m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in judged["checks"].items()}
    result["facts"] = facts
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", choices=("", "stale_read"), default="")
    ap.add_argument("--set", action="append", default=[], metavar="PART.KEY=JSON",
                    help="override a config or traffic key (builder sweeps)")
    args = ap.parse_args(argv)
    scale = {}
    for item in args.set:
        key, _, value = item.partition("=")
        part, _, rest = key.partition(".")
        scale[(part, rest)] = json.loads(value)
    if not (spec.REPO / "crdt_tpu").is_dir():
        print("benchmark: no program beside the benchmark (crdt_tpu/ is "
              "missing)", file=sys.stderr)
        return 2
    result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                 rehearse=args.rehearse_cpu, control=args.control,
                 scale=scale)
    facts = result.pop("facts")
    for k, v in facts.items():
        say(f"# {k}: {json.dumps(v)}")
    checks = result.pop("checks")
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    if args.rehearse_cpu or args.control or scale:
        say("# builder run: no result line")
        say("# " + json.dumps(dict(result, checks=checks)))
        return 3
    result["checks"] = checks
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
