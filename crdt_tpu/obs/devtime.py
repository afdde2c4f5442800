"""Device-side attribution of join dispatches.

* :func:`dispatch_annotation` — a ``jax.profiler.TraceAnnotation`` keyed
  to the CURRENT TRACE ID (extending crdt_tpu.obs.trace.span, which keys
  by name only), so one gossip round's merge dispatch is findable in a
  profiler capture by the same ID that names its JSONL events.  Device
  time itself is read from the captured trace's device ops, never from
  the host's wall clock.
* :data:`PEAK_HBM_BYTES_PER_S` — the published peak HBM bandwidth table
  that roofline ratios divide by.
"""
from __future__ import annotations

from typing import Dict, Optional

from crdt_tpu.obs.trace import current_trace, region

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


def dispatch_annotation(name: str):
    """Profiler annotation for one device dispatch, keyed to the enclosing
    gossip round's trace ID — ``crdt.join.merge#trace=<id>`` — so a device
    profile row joins the fleet's JSONL timeline by ID, not just by name.
    Yields the label."""
    return region(f"crdt.join.{name}", current_trace())
