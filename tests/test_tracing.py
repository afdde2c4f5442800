"""The span facility (crdt_tpu.obs.trace) produces profiler traces, its
lock opens a span only on a contended acquisition, and its collector hook
spans generation-1/2 collections (SURVEY.md §5: the reference has no
observability at all; here jax.profiler is wired through obs.trace and
must work end to end on any backend)."""
import gc
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import pytest

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
    assert captured_spans(logdir) == [trace.GC_SPAN]
