"""The one span facility: profiler annotations on the served path, and
cross-node gossip trace IDs.

Every span is a ``jax.profiler.TraceAnnotation`` whose name starts with
``crdt.``: while a profiler session captures the process, the host span
lands in the same trace, on the same clock, as the device ops it
surrounds; with no session running it costs a microsecond or two and keeps
nothing.  Spans are opened per request, drain, contended lock
acquisition, round, dispatch or collection, never per op.

* :func:`region` — a plain named annotation (a context manager or a
  decorator);
* :func:`span` — a region that also binds a gossip round's trace ID.  A
  trace ID names ONE anti-entropy round end-to-end: the puller mints it
  (:func:`mint_trace_id`), sends it with the /gossip request in the
  ``X-CRDT-Trace`` header, and both sides record it in their event logs
  (crdt_tpu.obs.events), so a two-node pull produces event lines on both
  nodes sharing one ID;
* :class:`SpanLock` — a lock whose contended acquisitions are spans;
* :func:`install_gc_spans` — a span per generation-1/2 collection of
  Python's garbage collector;
* :func:`install_gil_probe` — a daemon thread whose fixed 1 ms sleeps are
  spans while a profiler session runs, so a span's length beyond 1 ms is
  how long a runnable Python thread waited to run again.

The span names, what each covers, and how to capture them are listed in
crdt_tpu/obs/README.md ("Spans on the served path").
"""
from __future__ import annotations

import contextlib
import contextvars
import gc
import itertools
import os
import threading
import time
from typing import Optional

TRACE_HEADER = "X-CRDT-Trace"

# process-unique prefix + atomic counter: IDs are unique across the fleet
# without coordination (the PID+random token disambiguates processes, the
# counter disambiguates rounds within one)
_PROC = f"{os.getpid():x}{os.urandom(3).hex()}"
_SEQ = itertools.count(1)
_SEQ_LOCK = threading.Lock()

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "crdt_trace", default=None
)


def mint_trace_id(rid: int = -1) -> str:
    """A fleet-unique trace ID for one gossip round."""
    with _SEQ_LOCK:
        n = next(_SEQ)
    return f"{rid:x}-{_PROC}-{n:x}" if rid >= 0 else f"{_PROC}-{n:x}"


def current_trace():
    """The trace ID of the enclosing ``span`` (None outside one)."""
    return _CURRENT.get()


@contextlib.contextmanager
def region(name: str, trace_id: Optional[str] = None):
    """A profiler annotation named ``name``, or ``name#trace=<id>`` when
    ``trace_id`` names the gossip round it serves; it mints and binds no
    trace ID.  Yields the annotation's name.  A context manager, and a
    decorator that opens a fresh annotation per call.  jax is imported on
    first use, so event-log-only consumers (the crash-soak report reader)
    never pay the import."""
    from jax.profiler import TraceAnnotation

    label = f"{name}#trace={trace_id}" if trace_id else name
    with TraceAnnotation(label):
        yield label


@contextlib.contextmanager
def span(name: str, trace_id=None):
    """Bind ``trace_id`` (or the enclosing one, or a fresh one) as
    current and open a same-named :func:`region`, so the host span and
    its device dispatches correlate by name in a captured trace.  Yields
    the trace ID."""
    tid = trace_id or current_trace() or mint_trace_id()
    token = _CURRENT.set(tid)
    try:
        with region(name):
            yield tid
    finally:
        _CURRENT.reset(token)


class SpanLock:
    """A ``threading.Lock`` whose CONTENDED acquisitions are spans: it
    first tries to take the lock without blocking and opens ``name``
    only when it has to wait, so the span covers exactly the wait and an
    uncontended acquisition records nothing.  Same ``acquire`` /
    ``release`` / ``with`` API as the lock it wraps."""

    __slots__ = ("_acquire", "release", "name")

    def __init__(self, name: str):
        # the wrapped lock's bound methods: acquire() returns holding the
        # lock by contract, so no call site here is a lock to release
        lock = threading.Lock()
        self._acquire = lock.acquire
        self.release = lock.release
        self.name = name

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._acquire(False):
            return True
        if not blocking:
            return False
        with region(self.name):
            return self._acquire(True, timeout)

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()


GC_SPAN = "crdt.py_gc"
# the open collection annotation of each thread (a collection starts and
# stops on the thread that triggered it)
_GC_OPEN = threading.local()


def _gc_callback(phase: str, info: dict) -> None:
    if info["generation"] == 0:
        return  # young collections are frequent and short: no span
    if phase == "start":
        ann = region(GC_SPAN)
        ann.__enter__()
        _GC_OPEN.ann = ann
    else:
        ann = getattr(_GC_OPEN, "ann", None)
        if ann is not None:
            _GC_OPEN.ann = None
            ann.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Open a ``crdt.py_gc`` span over every generation-1 and -2
    collection of this process (a ``gc.callbacks`` hook; installing it
    again is a no-op)."""
    if _gc_callback not in gc.callbacks:
        gc.callbacks.append(_gc_callback)


PROBE_SPAN = "crdt.probe.gil"
#: while a profiler session captures the process, the probe wakes once per
#: PROBE_PERIOD_S and sleeps a fixed PROBE_SLEEP_S inside its span; with no
#: session it only looks for one every PROBE_IDLE_S and opens nothing.  All
#: three are constants, not configuration
PROBE_PERIOD_S = 0.010
PROBE_SLEEP_S = 0.001
PROBE_IDLE_S = 1.0
_PROBE_LOCK = threading.Lock()
_PROBE: Optional[threading.Thread] = None


def _probe_once() -> float:
    """One turn of the probe: a span around the fixed sleep while a
    profiler session runs, nothing otherwise.  Returns how long to sleep
    before the next turn."""
    # a bare annotation: nothing but the sleep and the GIL re-take inside
    from jax.profiler import TraceAnnotation

    if not TraceAnnotation.is_enabled():
        return PROBE_IDLE_S
    with TraceAnnotation(PROBE_SPAN):
        time.sleep(PROBE_SLEEP_S)
    return PROBE_PERIOD_S - PROBE_SLEEP_S


def _probe_loop() -> None:
    while True:
        time.sleep(_probe_once())


def install_gil_probe() -> threading.Thread:
    """Start the interpreter-wait probe: one daemon thread that, while a
    profiler session captures this process, every 10 ms opens a
    ``crdt.probe.gil`` span, sleeps a fixed 1 ms (which releases the GIL)
    and closes it.  Closing the span needs the GIL again, so its length
    beyond 1 ms is how long a runnable Python thread of this process
    waited to run: contention for the GIL, or a stall of the whole host
    (a process that runs none of this code, sleeping beside it, tells the
    two apart).  With no session it wakes once a second to look for one,
    and spans nothing.  It draws no random numbers.  Installing it again
    returns the running thread."""
    global _PROBE
    with _PROBE_LOCK:
        if _PROBE is None:
            _PROBE = threading.Thread(target=_probe_loop,
                                      name="crdt-gil-probe", daemon=True)
            _PROBE.start()
        return _PROBE
