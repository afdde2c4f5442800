"""Double-buffered stripe execution: overlap host staging with device compute.

Each device dispatch pays a fixed host cost, and the striped big-shape
drivers (the 1M-lane OR-Set union, the capacity-striped lexN
engine) additionally pay HOST time per stripe — numpy packing, sorting,
``device_put`` — that the serial loop serializes with the device compute:

    serial:     [build 0][compute 0][build 1][compute 1]...
    pipelined:  [build 0][compute 0 | build 1][compute 1 | build 2]...

JAX dispatch is already asynchronous — a jitted call returns immediately
while the device works — so the pipeline needs no threads: dispatch
stripe i, stage stripe i+1 on the host while i is in flight, then block.
What this module adds on top of raw async dispatch is

* a BOUNDED in-flight window (``DispatchQueue``): unbounded run-ahead
  would stage every stripe's operands at once and OOM the 16 GB chip —
  depth=1 is exactly the double buffer (at most stripe i on device +
  stripe i+1's operands staged);
* dispatch accounting (``pipeline_dispatches``) and an occupancy gauge
  (``pipeline_occupancy``) on the shared metrics registry, so the
  dispatch-count assertions and the /metrics surface see the pipeline;
* a donation-safe ownership discipline: ``run_striped`` drops its
  reference to each stripe's operands at dispatch, so a ``dispatch``
  callback built with ``joins.donating`` may alias them freely (the
  stripe carry is consumed exactly once — see the donation rule in
  crdt_tpu.ops.joins).

Determinism: pipelining reorders only HOST work; every stripe's device
program and operands are identical to the serial schedule's, so outputs
are bit-equal (pinned by tests/test_pipeline.py).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

import jax

from crdt_tpu.obs import health


class DispatchQueue:
    """Bounded window of in-flight async device dispatches.

    ``submit`` issues one (async) dispatch and then blocks on the OLDEST
    in-flight result only once more than ``depth`` are outstanding.
    depth=1 is the double-buffer discipline; depth=0 degenerates to the
    serial schedule (every dispatch blocked immediately — the A/B
    reference arm).  ``wait_s`` accumulates the host time spent blocked
    in ``block_until_ready``; together with the caller's staging time it
    yields the pipeline-occupancy gauge.
    """

    def __init__(self, depth: int = 1, registry=None,
                 label: str = "pipeline"):
        self.depth = max(0, int(depth))
        self.registry = registry
        self.label = label
        self.dispatches = 0
        self.wait_s = 0.0
        self._in_flight: List[Any] = []
        self._done: List[Any] = []

    def submit(self, fn: Callable, *args: Any) -> None:
        out = fn(*args)  # async under jit: returns while the device works
        self.dispatches += 1
        if self.registry is not None:
            self.registry.inc("pipeline_dispatches", pipeline=self.label)
        self._in_flight.append(out)
        while len(self._in_flight) > self.depth:
            self._done.append(self._block(self._in_flight.pop(0)))

    def _block(self, out: Any) -> Any:
        t0 = time.perf_counter()
        out = jax.block_until_ready(out)
        self.wait_s += time.perf_counter() - t0
        return out

    def drain(self) -> List[Any]:
        """Block on everything still in flight; return ALL completed
        results in submission order and reset the queue."""
        while self._in_flight:
            self._done.append(self._block(self._in_flight.pop(0)))
        done, self._done = self._done, []
        return done


def run_striped(
    n_stripes: int,
    build: Callable[[int], Any],
    dispatch: Callable[..., Any],
    *,
    pipelined: bool = True,
    registry=None,
    pipeline: str = "stripe",
) -> Tuple[List[Any], Dict[str, float]]:
    """Run ``n_stripes`` stripes of ``build`` (host staging) + ``dispatch``
    (device compute), double-buffered when ``pipelined``.

    ``build(i)`` stages stripe i's operands on the host (numpy packing,
    ``device_put``); return a tuple to pass several operands.
    ``dispatch(i, *operands)`` issues the stripe's device work — it must
    NOT block (plain jitted calls are fine).  ``run_striped`` drops its
    only reference to the operands at dispatch, so a donating dispatch
    (crdt_tpu.ops.joins.donating) may alias them in place.

    Pipelined schedule: stripe i's device window overlaps ``build(i+1)``
    on the host; serial (``pipelined=False``) blocks each stripe before
    staging the next — byte-identical outputs, no overlap (the A/B
    reference arm for benches/bench_pipeline.py).

    Returns ``(results, stats)`` with results in stripe order and stats
    ``{stage_s, wait_s, occupancy, dispatches}``.  ``occupancy`` is the
    share of the dispatch-to-block window the host spent staging the next
    stripe instead of idling in ``block_until_ready`` (0.0 is reported
    for the serial schedule, where staging never overlaps the device).
    The stats are also pushed as gauges/counters when a ``registry`` is
    supplied (crdt_tpu.obs.health.observe_pipeline).
    """
    q = DispatchQueue(depth=1 if pipelined else 0, registry=registry,
                      label=pipeline)
    stage_s = 0.0
    for i in range(n_stripes):
        t0 = time.perf_counter()
        operands = build(i)
        stage_s += time.perf_counter() - t0
        if not isinstance(operands, tuple):
            operands = (operands,)
        # bind i statically; *operands is this scope's last reference, so
        # a donating dispatch owns the buffers outright
        q.submit(lambda *a, _i=i: dispatch(_i, *a), *operands)
        del operands
    results = q.drain()
    denom = stage_s + q.wait_s
    occupancy = (stage_s / denom) if (pipelined and denom > 0) else 0.0
    stats = {
        "stage_s": stage_s,
        "wait_s": q.wait_s,
        "occupancy": occupancy,
        "dispatches": q.dispatches,
    }
    if registry is not None:
        health.observe_pipeline(registry, pipeline, occupancy, n_stripes,
                                stage_s, q.wait_s)
    return results, stats
