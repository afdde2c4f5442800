"""Per-dispatch device-time attribution for join dispatches.

A regression in dispatch fusion (the PR 2 pipelined merge runtime) can
hide inside an unchanged end-to-end number unless the device side of
each join dispatch is attributed separately.  This module makes that
split scrapeable:

* :func:`dispatch_annotation` — a ``jax.profiler.TraceAnnotation`` keyed
  to the CURRENT TRACE ID (extending crdt_tpu.obs.trace.span, which keys
  by name only), so one gossip round's merge dispatch is findable in an
  xprof capture by the same ID that names its JSONL events;
* :func:`observe_join` — samples XLA's AOT ``cost_analysis()`` once per
  (function, operand-shape) signature and exports bytes-accessed / FLOPs
  gauges plus a live roofline ratio ``crdt_join_hbm_utilization`` =
  achieved HBM bandwidth / the device's published peak
  (:data:`PEAK_HBM_BYTES_PER_S`, keyed by ``device_kind``).  A device
  not in that table gets NO utilization gauge — counted loudly in
  ``crdt_join_peak_unknown_total`` — never an assumed default.
  Cost analysis runs on ``jax.ShapeDtypeStruct`` avals — never on live
  buffers, so donated operands (ops/joins.donating) are safe to key from
  after the dispatch consumed them.

The analysis lowering is a one-time cost per shape signature (shapes are
power-of-two bounded in api/node.py, so there are O(log n) signatures);
results are cached process-wide.  Backends whose compiled executables
expose no cost model degrade to timing-only histograms, counted loudly
in ``crdt_join_cost_analysis_unavailable_total``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional, Tuple

from crdt_tpu.obs.trace import current_trace

# Published peak HBM bandwidth per chip, bytes/s, keyed by the
# ``device_kind`` JAX reports.  Source: Google Cloud documentation, "TPU
# v5e" (16 GB HBM at 819 GB/s per chip).  The one table every roofline
# ratio in the repo divides by (benches/bench_baseline.py imports it).
PEAK_HBM_BYTES_PER_S: Dict[str, float] = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
}


def hbm_peak(device_kind: str) -> Optional[float]:
    """Published peak HBM bytes/s of ``device_kind``; None when unknown."""
    return PEAK_HBM_BYTES_PER_S.get(device_kind)

# (id(fn), operand aval signature) -> (flops, bytes_accessed) | None
_COST_CACHE: Dict[Tuple, Optional[Tuple[float, float]]] = {}

# gauge updates are SAMPLED 1-in-N per (node, kind): the cost gauges are
# last-write-wins and shapes only change on capacity growth, so paying
# the signature hash + three labeled set_gauge calls every dispatch buys
# nothing — the join_device histogram still sees every dispatch
GAUGE_SAMPLE_EVERY = 16
_dispatch_counts: Dict[Tuple[str, str], int] = {}


@contextlib.contextmanager
def dispatch_annotation(name: str, enabled: bool = True):
    """Profiler annotation for one device dispatch, keyed to the enclosing
    gossip round's trace ID — ``crdt.join.merge#trace=<id>`` — so a device
    profile row joins the fleet's JSONL timeline by ID, not just by name."""
    if not enabled:
        yield None
        return
    tid = current_trace()
    label = f"crdt.join.{name}" + (f"#trace={tid}" if tid else "")
    try:
        import jax
        ctx = jax.profiler.TraceAnnotation(label)
    except ImportError:  # pragma: no cover - jax is a hard dep in-tree
        ctx = contextlib.nullcontext()
    with ctx:
        yield label


def _aval_signature(args) -> Tuple:
    import jax

    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(args)
    )


def _cost_for(fn, args) -> Optional[Tuple[float, float]]:
    """(flops, bytes accessed) of ``fn(*args)``, from XLA's AOT cost
    analysis, cached per (fn, shape signature)."""
    import jax

    key = (id(fn), _aval_signature(args))
    if key in _COST_CACHE:
        return _COST_CACHE[key]
    try:
        specs = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), args
        )
        lower = getattr(fn, "lower", None)
        if lower is None:
            # backend-dispatch wrappers (ops/joins.donating) are plain
            # callables; an outer jit traces through to the inner one and
            # lowers the same computation (one-time per shape signature)
            lower = jax.jit(fn).lower
        analysis = lower(*specs).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        cost = (
            float(analysis.get("flops", 0.0)),
            float(analysis.get("bytes accessed", 0.0)),
        )
    except (AttributeError, KeyError, TypeError, ValueError,
            RuntimeError, NotImplementedError):
        cost = None
    _COST_CACHE[key] = cost
    return cost


def observe_join(registry, node_label: str, fn, args, seconds: float,
                 kind: str = "merge") -> None:
    """Attribute one completed (synced) join dispatch: always records the
    device-join latency histogram; when the backend exposes a cost model,
    additionally exports the per-dispatch FLOPs / bytes gauges and the
    roofline ratio against the device's :func:`hbm_peak` (gauges sampled
    1 in :data:`GAUGE_SAMPLE_EVERY` dispatches; the first always lands)."""
    if not getattr(registry, "enabled", False):
        return
    registry.observe("join_device", max(seconds, 0.0),
                     node=node_label, kind=kind)
    ckey = (node_label, kind)
    n = _dispatch_counts.get(ckey, 0)
    _dispatch_counts[ckey] = n + 1
    if n % GAUGE_SAMPLE_EVERY:
        return  # sampled out; first dispatch always lands the gauges
    cost = _cost_for(fn, args)
    if cost is None:
        registry.inc("join_cost_analysis_unavailable",
                     node=node_label, kind=kind)
        return
    flops, nbytes = cost
    registry.set_gauge("join_flops_per_dispatch", flops,
                       node=node_label, kind=kind)
    registry.set_gauge("join_bytes_per_dispatch", nbytes,
                       node=node_label, kind=kind)
    if seconds > 0 and nbytes > 0:
        import jax

        peak = hbm_peak(jax.devices()[0].device_kind)
        if peak is None:
            registry.inc("join_peak_unknown", node=node_label, kind=kind)
            return
        registry.set_gauge("join_hbm_utilization", nbytes / seconds / peak,
                           node=node_label, kind=kind)


class DispatchTimer:
    """Tiny helper pairing ``dispatch_annotation`` with a wall timer whose
    reading is only meaningful AFTER the caller synced the result (e.g.
    the ``int(n_unique)`` the merge path already pays)."""

    __slots__ = ("t0", "seconds")

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
