"""The span facility (crdt_tpu.obs.trace) produces profiler traces, its
lock opens a span only on a contended acquisition, its collector hook
spans generation-1/2 collections, and its interpreter-wait probe is one
daemon thread per process (SURVEY.md §5: the reference has no
observability at all; here jax.profiler is wired through obs.trace and
must work end to end on any backend)."""
import gc
import os
import pathlib
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import spec
from benchmark import trace as tracelib
from crdt_tpu.models import gcounter
from crdt_tpu.obs import trace
from crdt_tpu.parallel import swarm


def captured_spans(logdir: pathlib.Path):
    """Names of the crdt.* host spans in the one capture under logdir."""
    files = list(logdir.rglob("*.xplane.pb"))
    assert len(files) == 1, files
    return [name for _, _, name in tracelib.extract(str(files[0]))["spans"]]


def test_trace_to_captures_profile(tmp_path):
    logdir = tmp_path / "trace"
    s = swarm.make(gcounter.zero(8, batch=(64,)))
    with jax.profiler.trace(str(logdir)):
        with trace.region("crdt.converge"):
            out = swarm.converge(
                s, gcounter.join, gcounter.zero(8)
            )
            jax.block_until_ready(out.state.counts)
    assert captured_spans(logdir).count("crdt.converge") == 1


def test_trace_region_is_transparent():
    with trace.region("crdt.noop"):
        x = jnp.arange(4).sum()
    assert int(x) == 6


# ------------------------------------------------------------- SpanLock


@pytest.fixture
def opened(monkeypatch):
    """Record the name of every region SpanLock opens."""
    names = []
    real = trace.region

    def recording(name):
        names.append(name)
        return real(name)

    monkeypatch.setattr(trace, "region", recording)
    return names


def free(lock) -> bool:
    """The lock is not held (and stays so)."""
    if not lock.acquire(blocking=False):
        return False
    lock.release()
    return True


def _take(lock, mode):
    if mode == "with":
        with lock:
            pass
    else:
        assert lock.acquire()
        lock.release()


@pytest.mark.parametrize("mode", ["with", "acquire"])
def test_span_lock_uncontended_records_nothing(opened, mode):
    lock = trace.SpanLock("crdt.wait.test")
    for _ in range(3):
        _take(lock, mode)
    assert opened == []
    assert free(lock)


@pytest.mark.parametrize("mode", ["with", "acquire"])
def test_span_lock_contended_records_one_wait(opened, mode):
    lock = trace.SpanLock("crdt.wait.test")
    done = threading.Event()

    def waiter():
        _take(lock, mode)
        done.set()

    lock.acquire()
    t = threading.Thread(target=waiter)
    t.start()
    deadline = time.monotonic() + 10
    while not opened and time.monotonic() < deadline:
        time.sleep(0.001)
    assert opened == ["crdt.wait.test"]  # the waiter is blocked in its span
    assert not done.is_set()
    lock.release()
    t.join(timeout=10)
    assert not t.is_alive() and done.is_set()
    assert opened == ["crdt.wait.test"]
    assert free(lock)


def test_span_lock_nonblocking_and_timeout(opened):
    lock = trace.SpanLock("crdt.wait.test")
    assert lock.acquire(blocking=False)
    assert not lock.acquire(blocking=False)  # refused, nothing waited
    assert opened == []
    assert not lock.acquire(timeout=0.01)    # waited, then gave up
    assert opened == ["crdt.wait.test"]
    lock.release()
    assert free(lock)


# ------------------------------------------------------------- GC hook


def test_gc_spans_one_per_old_collection(tmp_path):
    trace.install_gc_spans()
    trace.install_gc_spans()
    assert gc.callbacks.count(trace._gc_callback) == 1
    logdir = tmp_path / "trace"
    was = gc.isenabled()
    gc.disable()  # no collection but the ones asked for below
    try:
        with jax.profiler.trace(str(logdir)):
            gc.collect(0)
            gc.collect(2)
    finally:
        if was:
            gc.enable()
    # the probe thread runs in any process that booted a NodeHost
    got = [n for n in captured_spans(logdir) if n != trace.PROBE_SPAN]
    assert got == [trace.GC_SPAN]


# -------------------------------------------------- interpreter-wait probe


def _probes():
    return [t for t in threading.enumerate() if t.name == "crdt-gil-probe"]


def test_gil_probe_starts_once_as_a_daemon():
    first = trace.install_gil_probe()
    assert trace.install_gil_probe() is first
    assert first.daemon and first.is_alive()
    assert _probes() == [first]


def test_gil_probe_spans_its_sleeps(tmp_path):
    """Each probe span holds the fixed 1 ms sleep; the benchmark's reader
    subtracts exactly that sleep from the probe span's mean."""
    trace.install_gil_probe()
    logdir = tmp_path / "trace"
    with jax.profiler.trace(str(logdir)):
        # the probe looks for a session once per PROBE_IDLE_S
        time.sleep(trace.PROBE_IDLE_S + 0.3)
    files = list(logdir.rglob("*.xplane.pb"))
    spans = [(s, e) for s, e, n in tracelib.extract(str(files[0]))["spans"]
             if n == trace.PROBE_SPAN]
    assert len(spans) >= 5
    assert all(e - s >= trace.PROBE_SLEEP_S * 1e9 for s, e in spans)
    starts = sorted(s for s, _ in spans)
    assert min(b - a for a, b in zip(starts, starts[1:])) \
        >= trace.PROBE_PERIOD_S * 1e9 * 0.9
    reader = spec.layer_reader("gil_wait_ms.sat")
    assert reader.PROBE_SPAN == trace.PROBE_SPAN
    assert reader.SLEEP_MS == trace.PROBE_SLEEP_S * 1e3


def test_gil_probe_spans_only_in_a_session(tmp_path):
    """With no profiler session a turn of the probe opens no span and
    sleeps PROBE_IDLE_S; inside one it spans its fixed sleep and comes
    back a period later."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    t = time.perf_counter()
    assert trace._probe_once() == trace.PROBE_IDLE_S
    assert time.perf_counter() - t < trace.PROBE_IDLE_S
    logdir = tmp_path / "trace"
    with jax.profiler.trace(str(logdir)):
        t = time.perf_counter()
        assert trace._probe_once() == pytest.approx(
            trace.PROBE_PERIOD_S - trace.PROBE_SLEEP_S)
        assert time.perf_counter() - t >= trace.PROBE_SLEEP_S
    assert trace.PROBE_SPAN in captured_spans(logdir)


def test_gil_probe_stops_with_its_process():
    """A process that started the probe exits on its own: the probe
    thread never holds it up."""
    code = ("import time\n"
            "from crdt_tpu.obs import trace\n"
            "t = trace.install_gil_probe()\n"
            "time.sleep(0.1)\n"
            "assert t.is_alive() and t.daemon\n"
            "print('probe up')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                         capture_output=True, text=True,
                         cwd=pathlib.Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "probe up"
