"""Headline benchmark: G-Counter replica-merges/sec on one chip.

BASELINE.md north star: >=100M G-Counter replica-merges/sec on a single v5e
chip (the reference's merge hot path, /root/reference/main.go:35-100, runs at
~0.67 merges/sec/replica over loopback HTTP; here one fused elementwise-max
over a (replicas, nodes) plane merges the whole swarm per call).

Measurement notes:
* Each call pays a fixed dispatch + host-sync cost, so K merges are
  chained inside ONE jitted fori_loop and the per-merge time is the
  difference quotient between two K values (the fixed cost cancels).
* XLA's algebraic simplifier collapses loops of idempotent `max(x, b)` (and
  even `max(x, b + i)`) into O(1) work, which silently benchmarks nothing.
  The loop body therefore joins against a BANK of distinct peer states
  selected by dynamic index (`B[i % BANK]`) — data-dependent, so no
  algebraic collapse is possible, with the same 2-read/1-write memory
  traffic as a real merge.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "p50_merge_latency_us": N, "p99_merge_latency_us": N,
   "latency_samples": N, "device": {...}, "obs": {...}}
"device" is what JAX ran on (platform, device_kind, count): a run on the
CPU is a rehearsal and says so there — its rate is never a chip number.
The "obs" key is the run's registry snapshot (crdt_tpu.obs): the latency
samples also stream through a mergeable log2-bucket histogram, so the
driver can fold many runs' histograms elementwise instead of re-deriving
quantiles from raw sample lists.
vs_baseline is value / 100e6 (the BASELINE target; the reference publishes
no numbers of its own — BASELINE.md "published: none").  The latency
quantiles answer the second half of the north-star metric ("p50 merge
latency"): each sample is an independent paired-difference estimate of the
time for ONE full 1M-replica merge (same bank-of-peers loop), so p50/p99
are quantiles over device-timed per-merge samples, in microseconds.
"""
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

TARGET = 100e6   # replica-merges/sec, BASELINE.md north star
R = 1 << 20      # 1M replicas (north-star scale)
N_NODES = 8
BANK = 16        # distinct peer states cycled through the loop
K_SMALL, K_LARGE = 64, 512
REPS = 7
QUANTILE_REPS = 120  # latency-quantile sample count at the final K pair
# >=100 samples so "p99" is an actual tail quantile rather than the max of
# a handful of draws (round-2 verdict: 15 samples made p99 a max-label)


@partial(jax.jit, static_argnames="k")
def chained_merges(a, bank, k):
    def body(i, x):
        peer = jax.lax.dynamic_index_in_dim(bank, i % BANK, keepdims=False)
        return jnp.maximum(x, peer)

    out = jax.lax.fori_loop(0, k, body, a)
    return out.sum()  # 8-byte result; fetching it forces completion


# the K-delta must dwarf dispatch jitter AND slow drift.  Sized in round 5
# for a remote chip; on a local chip the jitter is smaller, but that has
# not been measured, so the floor is kept (it only costs a larger K)
MIN_DIFF_S = 0.15


def _once(a, bank, k):
    t0 = time.perf_counter()
    _ = int(chained_merges(a, bank, k))
    return time.perf_counter() - t0


def paired_diffs(a, bank, k_small, k_large, reps=REPS):
    """Sorted INTERLEAVED (t_large - t_small) pairs: chip throughput
    drifts over seconds, so measuring all-small then all-large bakes the
    drift into the quotient; back-to-back pairs cancel it.  Each diff is an
    independent device-timed estimate of (k_large - k_small) merges."""
    _ = int(chained_merges(a, bank, k_small))  # compile + warm both
    _ = int(chained_merges(a, bank, k_large))
    return sorted(
        _once(a, bank, k_large) - _once(a, bank, k_small)
        for _ in range(reps)
    )


def _quantile(sorted_xs, q):
    """Nearest-rank quantile of an ascending list (no numpy dependency)."""
    i = min(len(sorted_xs) - 1, max(0, round(q * (len(sorted_xs) - 1))))
    return sorted_xs[int(i)]


def _kernel_gate():
    """Refuse to produce a headline number on a real accelerator whose
    compiled Pallas kernels disagree with the XLA oracles.  Interpret-mode
    CI cannot catch Mosaic lowering breaks; this can.  Any disagreement
    raises, so a kernel regression cannot ship a number.

    The gated subset covers EVERY fused path (OR-combine, lex2, columnar
    OpLog, shard_map sharded_converge, lexN RSeq, GC-aware RSeq join,
    sharded GC-aware converge); its log goes to stderr (a chip run must
    leave the checkout clean).  On the CPU the gate is skipped: the
    kernels are covered interpret-mode by tests/, and the run is a
    rehearsal whose "device" field says cpu."""
    if jax.default_backend() == "cpu":
        return
    from benches import hw_selftest

    def log(*a, **kw):
        print(*a, **dict(kw, file=sys.stderr))

    hw_selftest.run(full=False, log=log)


def main():
    from crdt_tpu.utils import compile_cache

    compile_cache.enable()
    _kernel_gate()
    ka, kb = jax.random.split(jax.random.key(0))
    a = jax.random.randint(ka, (R, N_NODES), 0, 1 << 20, dtype=jnp.int32)
    bank = jax.random.randint(kb, (BANK, R, N_NODES), 0, 1 << 20, dtype=jnp.int32)

    # adaptive K: grow until the time delta dwarfs dispatch jitter.  dk is
    # captured WITH its diff — pairing the last diff with a post-scaled
    # K-delta would inflate the result 4x on loop exhaustion
    k_small, k_large = K_SMALL, K_LARGE
    for _ in range(4):
        diffs = paired_diffs(a, bank, k_small, k_large)
        diff = diffs[len(diffs) // 2]
        dk = k_large - k_small
        if diff >= MIN_DIFF_S:
            break
        k_small, k_large = k_small * 4, k_large * 4
    else:
        print(
            f"# WARNING: diff {diff:.3e}s never cleared the {MIN_DIFF_S}s "
            f"noise floor (K up to {k_large}); rate below is unreliable",
            file=sys.stderr,
        )

    # latency quantiles at the settled K pair: more independent samples of
    # the same paired-difference estimator, each divided by dk = seconds
    # for ONE full 1M-replica merge (device-timed; fixed cost cancelled)
    samples = paired_diffs(a, bank, k_small, k_large, reps=QUANTILE_REPS)
    per_merge_samples = [max(d, 1e-9) / dk for d in samples]
    p50 = _quantile(per_merge_samples, 0.50)
    p99 = _quantile(per_merge_samples, 0.99)

    # end-of-run registry snapshot: the same samples through the mergeable
    # histogram (crdt_tpu.obs) — fold-able across runs by the driver
    from crdt_tpu.obs.registry import MetricsRegistry

    obs = MetricsRegistry()
    for s in per_merge_samples:
        obs.observe("merge", s)
    obs.inc("bench_runs")

    merges_per_sec = R / p50
    dev = jax.devices()[0]
    print(
        json.dumps(
            {
                "metric": "gcounter_replica_merges_per_sec_1M",
                "value": round(merges_per_sec, 1),
                "unit": "replica-merges/s",
                "vs_baseline": round(merges_per_sec / TARGET, 3),
                "p50_merge_latency_us": round(p50 * 1e6, 3),
                "p99_merge_latency_us": round(p99 * 1e6, 3),
                "latency_samples": len(per_merge_samples),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices())},
                "obs": {k: round(v, 6) for k, v in obs.snapshot().items()},
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
