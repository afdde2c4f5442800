"""Full BASELINE suite: every target config from BASELINE.md, one JSON line
each (same schema as bench.py), optionally rendered into BENCH_TABLE.md.

Configs (BASELINE.md "Target configs"):
  gcounter_pair      2-replica increment+merge (the reference's default path,
                     /root/reference/main.go:35-100) — single-merge latency.
  pncounter_vmap_1k  1K replicas, batched vector join (vmap elementwise max).
  lww_argmax_100k    100K registers, (ts, rid) lexicographic argmax join.
  orset_union        columnar Pallas sorted-segment union (BASELINE shape is
                     1M x 1K; default here is HBM-safe and the rate scales
                     linearly in lanes — override with --lanes).
  gossip_allreduce   10K-replica swarm: full convergence (tree-reduced join
                     fixpoint) per step — one step == the gossip fixpoint the
                     reference needs many 1500 ms rounds to reach.

Timing uses the same difference quotient as bench.py: K work-steps chained
inside ONE jitted fori_loop, per-step time = difference quotient between two
K values (the fixed dispatch + host-sync cost cancels).  Every loop body consumes a
bank of distinct peer states via dynamic indexing so XLA cannot algebraically
collapse the idempotent joins (see bench.py header).

Usage:
  python benches/bench_baseline.py                 # full suite on the chip
  python benches/bench_baseline.py --write-md      # also refresh BENCH_TABLE.md
  python benches/bench_baseline.py --tiny --cpu    # CI smoke (tests/)
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from functools import partial

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

REPO = pathlib.Path(__file__).resolve().parent.parent
REPS = 5


# Floor on the K-delta of the difference quotient: t(k_large) - t(k_small)
# must exceed it before a rate is reported.  Chosen as ~10x the host's
# dispatch + sync jitter on a local chip, which has NOT been measured
# (no chip run of this suite since the round-5 records); treat it as a
# guard against reporting pure dispatch noise, not a calibrated bound.
MIN_DIFF_S = 0.02

# Bandwidth columns: % of the running device's published HBM peak, from
# the one peak table (crdt_tpu.obs.devtime.PEAK_HBM_BYTES_PER_S, keyed by
# device_kind).  A device not in the table gets no % column.  v5e VMEM is
# 128 MB.  A fori_loop whose carry fits comfortably in VMEM pays HBM only
# for the peer plane it streams per step (measured: a 32 MB carry ran the
# 3-logical-plane loop at 45 us/step = 0.73 TB/s counting ONE plane); a
# carry past ~100 MB pays all three planes (measured 0.68 TB/s, 83% of
# spec, benches/pn_diag.py) — round-5 chip records.
VMEM_CARRY_BUDGET = 100 * (1 << 20)


def _hbm_bytes_per_step(state_bytes):
    """Per-step HBM traffic model for the bank-of-peers max-join loops:
    read self + read peer + write result when the carry lives in HBM;
    peer-plane read only when the carry is VMEM-resident."""
    if state_bytes > VMEM_CARRY_BUDGET:
        return 3 * state_bytes
    return state_bytes


def _timed(fn, k_small, k_large, reps=REPS, min_diff=MIN_DIFF_S):
    """Best-of-reps difference quotient: seconds per work-step.

    Adaptive: if t(k_large) - t(k_small) is inside the dispatch-jitter floor
    (small configs finish thousands of loop steps inside the dispatch
    noise), quadruple both K values and retry, so the measured delta is
    always dominated by on-device work."""

    def run(k):
        fn(k)  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(k)
            best = min(best, time.perf_counter() - t0)
        return best

    for _ in range(6):
        t1, t2 = run(k_small), run(k_large)
        if t2 - t1 >= min_diff:
            break
        k_small, k_large = k_small * 4, k_large * 4
    else:
        if min_diff > 0:
            print(
                f"# WARNING: diff {t2 - t1:.2e}s never cleared the "
                f"{min_diff}s noise floor (K up to {k_large}); "
                "rate below is an upper bound, not a measurement",
                file=sys.stderr,
            )
    return max((t2 - t1) / (k_large - k_small), 1e-12)


def device_info():
    """The device every emitted row ran on, as JAX reports it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_tb_s():
    """Published HBM peak of the running device in TB/s, or None."""
    import jax

    from crdt_tpu.obs.devtime import hbm_peak

    peak = hbm_peak(jax.devices()[0].device_kind)
    return None if peak is None else peak / 1e12


# end-of-run observability snapshot (crdt_tpu.obs): every emitted row
# counts, and measured step times feed a mergeable histogram — the suite's
# own telemetry rides the same registry the nodes expose on GET /metrics
from crdt_tpu.obs.registry import MetricsRegistry

OBS = MetricsRegistry()


def _emit(results, name, value, unit, note, bytes_per_step=None,
          sec_per_step=None, traffic_kind="hbm", dispatches=None):
    """One JSON line per config.  When the caller supplies its per-step
    traffic model (bytes_per_step) and the measured step time, the line
    carries bytes-moved + effective TB/s + %-of-peak-HBM columns, so
    a config sitting 5x off its roofline is visible the round it happens
    (round-4 verdict weak #2: the PN 1M regression stayed latent for four
    rounds because only merges/s was recorded).  traffic_kind="compute"
    marks kernel-family rows whose bound is the VPU, not HBM (their TB/s
    is expected to sit far below spec -- see PERF.md roofline).
    ``dispatches`` records the config's device-dispatch count per logical
    work unit, so dispatch-bound rows are auditable from the JSON alone.
    Every row names the device it ran on (``device``)."""
    line = {"metric": name, "value": round(value, 1), "unit": unit,
            "vs_baseline": None, "note": note}
    line["device"] = device_info()
    if bytes_per_step is not None and sec_per_step:
        eff = bytes_per_step / sec_per_step / 1e12
        line["hbm_mb_per_step"] = round(bytes_per_step / (1 << 20), 1)
        line["eff_tb_s"] = round(eff, 3)
        peak = peak_tb_s()
        if peak is not None:
            line["pct_hbm_spec"] = round(100 * eff / peak, 1)
        line["traffic_kind"] = traffic_kind
    if dispatches is not None:
        line["device_dispatches"] = int(dispatches)
    print(json.dumps(line), flush=True)
    results.append(line)
    OBS.inc("bench_rows")
    if sec_per_step:
        OBS.observe("bench_step", sec_per_step)


# ---- configs ----------------------------------------------------------------


def bench_gcounter_pair(results, tiny):
    """2-replica merge latency: one pairwise G-Counter join (8 writer slots),
    the reference's whole merge() hot path (main.go:35-100) as one fused op."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.models import gcounter

    bank_n, nodes = 16, 8
    ks = jax.random.split(jax.random.key(1), 2)
    a = gcounter.GCounter(
        jax.random.randint(ks[0], (nodes,), 0, 1 << 20, dtype=jnp.int32))
    bank = jax.random.randint(ks[1], (bank_n, nodes), 0, 1 << 20,
                              dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(c, bank, k):
        def body(i, x):
            peer = jax.lax.dynamic_index_in_dim(bank, i % bank_n,
                                                keepdims=False)
            return jnp.maximum(x, peer)

        return jax.lax.fori_loop(0, k, body, c.counts).sum()

    ks_, kl = (8, 32) if tiny else (256, 2048)
    per = _timed(lambda k: int(chained(a, bank, k)), ks_, kl,
                 min_diff=0 if tiny else MIN_DIFF_S)
    # 32 B state: dispatch/issue-bound, no meaningful bandwidth column
    _emit(results, "gcounter_pair_merge_latency", per * 1e9, "ns/merge",
          "2-replica increment+merge, 8 writer slots (reference default path)")


def bench_pncounter_vmap(results, tiny, r=None, bank_n=8, suffix=""):
    """1K replicas, batched PN-Counter join: both planes, one fused max.
    Reused at 1M replicas (bench_pncounter_1m) for the north-star-scale
    datapoint.

    The peer bank is stored as SEPARATE pos/neg banks so each
    dynamic_index_in_dim feeds exactly one maximum and fuses as its
    producer.  The round-1..4 layout -- one (bank_n, 2, r, nodes) bank
    sliced once then split with peer[0]/peer[1] -- materialized a full
    (2, r, nodes) peer temp every step; at the 1M config that is 512 MB
    of extra HBM write+read per step, measured at 3.91 -> 2.34 ms/step
    when removed (2.69e8 -> 4.49e8 merges/s; `benches/pn_diag.py`, the
    round-4 verdict's weak #1)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.models import pncounter

    r = r or (64 if tiny else 1024)
    nodes = 64
    ks = jax.random.split(jax.random.key(2), 4)
    c = pncounter.PNCounter(
        pos=jax.random.randint(ks[0], (r, nodes), 0, 1 << 20, dtype=jnp.int32),
        neg=jax.random.randint(ks[1], (r, nodes), 0, 1 << 20, dtype=jnp.int32),
    )
    bank_pos = jax.random.randint(ks[2], (bank_n, r, nodes), 0, 1 << 20,
                                  dtype=jnp.int32)
    bank_neg = jax.random.randint(ks[3], (bank_n, r, nodes), 0, 1 << 20,
                                  dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(c, bank_pos, bank_neg, k):
        def body(i, x):
            j = i % bank_n
            peer = pncounter.PNCounter(
                pos=jax.lax.dynamic_index_in_dim(bank_pos, j, keepdims=False),
                neg=jax.lax.dynamic_index_in_dim(bank_neg, j, keepdims=False),
            )
            return pncounter.join(x, peer)

        out = jax.lax.fori_loop(0, k, body, c)
        return out.pos.sum() - out.neg.sum()

    ks_, kl = (8, 32) if tiny else ((64, 512) if r >= 1 << 20 else (256, 2048))
    per = _timed(lambda k: int(chained(c, bank_pos, bank_neg, k)), ks_, kl,
                 min_diff=0 if tiny else MIN_DIFF_S)
    state_bytes = 2 * r * nodes * 4
    _emit(results, f"pncounter_vmap_replica_merges_per_sec{suffix}", r / per,
          "replica-merges/s", f"{r}-replica batched PN join, {nodes} slots",
          bytes_per_step=_hbm_bytes_per_step(state_bytes), sec_per_step=per)


def bench_pncounter_1m(results, tiny):
    """North-star-scale PN point (VERDICT round 1 #9): 1M replicas x 64
    slots x 2 planes.  Bank shrinks to 4 peers: 4 x 2 x 1M x 64 x 4 B =
    2 GB resident."""
    bench_pncounter_vmap(
        results, tiny, r=(256 if tiny else 1 << 20), bank_n=4, suffix="_1m"
    )


def bench_lww_argmax(results, tiny, r=None, bank_n=8, suffix="", note=""):
    """100K registers: lexicographic (ts, rid) argmax select join.  Reused
    at 32M registers (bench_lww_32m) for the streaming-size datapoint.

    The register planes are 2-D ``(r // 128, 128)`` at streaming sizes:
    the chip's measured layout sweep (PERF.md) shows flat 1-D collapses to
    ~0.26 TB/s while any 2-D lane-aligned layout streams at 83-89% of
    spec.  The bank stays a pytree of separate ts/rid/payload banks so
    each dynamic slice fuses as the producer of its select (the PN 1M
    peer-bank-temp lesson, `benches/pn_diag.py`)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.models import lww

    r = r or (1 << 10 if tiny else 100_352)  # 98 * 1024 (lane-aligned ~100K)
    # 2-D only at streaming sizes: the committed 100K row was measured on
    # the 1-D layout (dispatch-dominated there, so layout is immaterial —
    # but don't silently change a committed row's conditions).
    shape = ((r // 128, 128)
             if r % 128 == 0 and 3 * r * 4 > VMEM_CARRY_BUDGET else (r,))
    ks = jax.random.split(jax.random.key(3), 4)

    def rand_reg(kt, kr, kp, shape):
        return lww.LWWRegister(
            ts=jax.random.randint(kt, shape, 0, 1 << 20, dtype=jnp.int32),
            rid=jax.random.randint(kr, shape, 0, 64, dtype=jnp.int32),
            payload=jax.random.randint(kp, shape, 0, 1 << 20, dtype=jnp.int32),
        )

    a = rand_reg(ks[0], ks[1], ks[2], shape)
    bks = jax.random.split(ks[3], 3)
    bank = rand_reg(bks[0], bks[1], bks[2], (bank_n,) + shape)

    @partial(jax.jit, static_argnames="k")
    def chained(a, bank, k):
        def body(i, x):
            peer = jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, i % bank_n,
                                                       keepdims=False), bank)
            return lww.join(x, peer)

        out = jax.lax.fori_loop(0, k, body, a)
        return out.ts.sum() + out.payload.sum()

    ks_, kl = (8, 32) if tiny else ((32, 256) if r >= 1 << 23 else (128, 1024))
    per = _timed(lambda k: int(chained(a, bank, k)), ks_, kl,
                 min_diff=0 if tiny else MIN_DIFF_S)
    _emit(results, f"lww_argmax_replica_merges_per_sec{suffix}", r / per,
          "replica-merges/s",
          note or f"{r}-register (ts, rid) argmax join",
          bytes_per_step=_hbm_bytes_per_step(3 * r * 4), sec_per_step=per)


def bench_lww_32m(results, tiny):
    """Streaming-size LWW point: 32M registers x 3 planes = 384 MB state
    (decisively past BOTH the VMEM carry budget and physical VMEM, so
    every step pays read-self + read-peer + write on all three planes).
    Exists so the counter-family 'HBM-bound at streaming sizes' claim is
    MEASURED for the register lattice too -- the 100K row is
    dispatch-dominated (1.1 MB state) and its low %-spec is otherwise
    easy to misread as a regression.  32M, not 16M: at 16M the PACKED
    sibling's carry is exactly the 128 MB physical VMEM and measurements
    flip-flop 9x between resident and spilled runs (benches/lww_diag.py
    header); both configs sit at the same register count so the packed
    speedup is apples-to-apples."""
    bench_lww_argmax(
        results, tiny, r=(1 << 14 if tiny else 1 << 25), bank_n=4,
        suffix="_32m",
        note=("33554432-register (ts, rid) argmax join, (262144, 128) "
              "2-D planes" if not tiny else None),
    )


def bench_lww_32m_packed(results, tiny):
    """The packed LWW fast path at the 32M-register streaming shape: the
    (ts, rid) pair packed order-preservingly into ONE key plane
    (lww.pack/join_packed), so each step streams 6 planes instead of 9
    and resolves with one compare instead of the cross-plane mask.
    Diagnosis that motivated it: `benches/lww_diag.py` (the mask program
    alone costs +37% over plain maxima on identical streams)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.models import lww

    r = 1 << 14 if tiny else 1 << 25
    bank_n = 4
    rid_bits = 7  # bench rids span [0, 64): one past the default-6 budget
    shape = (r // 128, 128)
    ks = jax.random.split(jax.random.key(3), 4)

    def rand_reg(kt, kr, kp, shape):
        return lww.LWWRegister(
            ts=jax.random.randint(kt, shape, 0, 1 << 20, dtype=jnp.int32),
            rid=jax.random.randint(kr, shape, 0, 64, dtype=jnp.int32),
            payload=jax.random.randint(kp, shape, 0, 1 << 20, dtype=jnp.int32),
        )

    a = rand_reg(ks[0], ks[1], ks[2], shape)
    assert bool(lww.pack_budget_ok(a, rid_bits))
    pa = lww.pack(a, rid_bits)
    bks = jax.random.split(ks[3], 3)
    bank = lww.pack(rand_reg(bks[0], bks[1], bks[2], (bank_n,) + shape),
                    rid_bits)

    @partial(jax.jit, static_argnames="k")
    def chained(pa, bank_key, bank_pay, k):
        def body(i, x):
            peer = lww.PackedLWW(
                key=jax.lax.dynamic_index_in_dim(bank_key, i % bank_n,
                                                 keepdims=False),
                payload=jax.lax.dynamic_index_in_dim(bank_pay, i % bank_n,
                                                     keepdims=False),
                rid_bits=x.rid_bits,
            )
            return lww.join_packed(x, peer)

        out = jax.lax.fori_loop(0, k, body, pa)
        return out.key.sum() + out.payload.sum()

    ks_, kl = (8, 32) if tiny else (32, 256)
    per = _timed(lambda k: int(chained(pa, bank.key, bank.payload, k)),
                 ks_, kl, min_diff=0 if tiny else MIN_DIFF_S)
    _emit(results, "lww_packed_replica_merges_per_sec_32m", r / per,
          "replica-merges/s",
          f"{r}-register packed-key argmax join (1 key + 1 payload plane)",
          bytes_per_step=_hbm_bytes_per_step(2 * r * 4), sec_per_step=per)


_CHAINED_FN_CACHE: dict = {}  # (c, ln, bank_n, interpret, donate) -> jitted chain


def _orset_union_rate(seed, c, ln, tiny, bank_n=None, chained_fn_cache=None):
    """Measured per-union seconds for a C-tag x ln-lane columnar union
    (None off-TPU after an interpret-mode smoke union).  Shared by the
    single-shape bench, the lane sweep, and the 1M striped driver.

    ``chained_fn_cache`` defaults to the shared module-level cache: ONE
    jitted chain per (c, ln, bank_n) so the 8-stripe 1M driver compiles
    once, not once per stripe."""
    import jax
    import jax.numpy as jnp

    if chained_fn_cache is None:
        chained_fn_cache = _CHAINED_FN_CACHE

    from crdt_tpu.ops import pallas_union
    from crdt_tpu.utils.constants import SENTINEL

    # HBM budget (v5e: 16 GB): inputs 2·C·ln·4 B (a) + bank_n·2·C·ln·4 B,
    # outputs 2·C·ln·4 B transient (out_size=C in-kernel truncation), PLUS
    # the fori_loop carry (2 planes).  On donating backends the (ka, va)
    # carry SEEDS are donated too (crdt_tpu.ops.joins donation rule): the
    # timed call then owns its carry outright and XLA writes the loop in
    # place — each rep passes a fresh jnp.copy of the seeds, whose cost is
    # identical at both K values and cancels in the difference quotient.
    # At 256K lanes a C=1024 plane is 1 GB and a two-peer bank would push
    # the working set past ~12 GB (it OOM'd with residue from earlier
    # sweep points), so shrink the bank to ONE peer there — the loop body
    # stays collapse-proof because pallas_call is an opaque custom call
    # XLA cannot algebraically simplify (unlike jnp.maximum).
    if bank_n is None:
        bank_n = 1 if c * ln * 4 >= (1 << 30) else 2
    interpret = jax.default_backend() != "tpu"
    from crdt_tpu.ops.joins import _DONATING_BACKENDS

    donate = (0, 1) if jax.default_backend() in _DONATING_BACKENDS else ()

    def cols(key, fill):
        ks = jax.random.randint(key, (c, ln), 0, 1 << 30, dtype=jnp.int32)
        ks = jax.lax.sort(ks, dimension=0)
        keys = jnp.where(jnp.arange(c)[:, None] < fill, ks, SENTINEL)
        return keys, (ks & 1).astype(jnp.int32)

    kk = jax.random.split(jax.random.key(seed), bank_n + 1)
    ka, va = cols(kk[0], c // 2)
    bank = [cols(k2, c // 2) for k2 in kk[1:]]
    bank_k = jnp.stack([b[0] for b in bank])
    bank_v = jnp.stack([b[1] for b in bank])

    cache_key = (c, ln, bank_n, interpret, donate)
    if cache_key not in chained_fn_cache:
        @partial(jax.jit, static_argnames="k", donate_argnums=donate)
        def chained(ka, va, bank_k, bank_v, k):
            def body(i, carry):
                kx, vx = carry
                j = i % bank_n
                kb = jax.lax.dynamic_index_in_dim(bank_k, j, keepdims=False)
                vb = jax.lax.dynamic_index_in_dim(bank_v, j, keepdims=False)
                ko, vo, _ = pallas_union.sorted_union_columnar(
                    kx, vx, kb, vb, out_size=c, interpret=interpret)
                return ko, vo

            ko, vo = jax.lax.fori_loop(0, k, body, (ka, va))
            return ko.sum() + vo.sum()

        chained_fn_cache[cache_key] = chained
    chained = chained_fn_cache[cache_key]

    if interpret:
        # interpret-pallas inside fori_loop is pathologically slow: one eager
        # union proves the path; skip the rate measurement off-TPU
        out = pallas_union.sorted_union_columnar(
            ka, va, bank_k[0], bank_v[0], out_size=c, interpret=True)
        jax.block_until_ready(out)
        return None
    ks_, kl = (2, 6) if tiny else (8, 32)
    if donate:
        # donated seeds are DELETED at dispatch: hand each timed call its
        # own copy (cost cancels across the two K values)
        def run(k):
            return int(chained(jnp.copy(ka), jnp.copy(va),
                               bank_k, bank_v, k))
    else:
        def run(k):
            return int(chained(ka, va, bank_k, bank_v, k))
    per = _timed(run, ks_, kl, min_diff=0 if tiny else MIN_DIFF_S)
    # free this shape's operands before the caller builds the next stripe/
    # sweep point; gc.collect() breaks any lingering cycles so the device
    # buffers actually release (the 256K point needs the headroom)
    del ka, va, bank_k, bank_v, bank
    import gc

    gc.collect()
    return per


def bench_orset_union(results, tiny, lanes=None, capacity=None):
    """Columnar Pallas sorted-segment union (BASELINE hard config)."""
    c = capacity or (64 if tiny else 1024)
    ln = lanes or (128 if tiny else 1 << 17)  # 128K lanes is HBM-safe
    per = _orset_union_rate(4, c, ln, tiny)
    if per is None:
        _emit(results, "orset_pallas_union_smoke", 1, "ok",
              f"interpret-mode union C={c} lanes={ln} (no TPU)")
        return
    _emit(results, "orset_pallas_replica_unions_per_sec", ln / per,
          "replica-unions/s",
          f"bitonic-merge union, C={c} tags x {ln} replicas "
          f"(1M-lane BASELINE shape measured by the striped driver below; "
          f"linearity measured by --sweep)",
          bytes_per_step=6 * c * ln * 4, sec_per_step=per,
          traffic_kind="compute")


def bench_orset_sweep(results, tiny):
    """Measured lane sweep (64K -> 128K -> 256K at C=1024): the evidence
    for lane-linearity that round 1 merely asserted.  The sweep tops out
    at 256K lanes: at C=1024 each (C, L) plane is 1 GB there, and the
    chained-loop working set (operands + peer bank + loop carry, which
    cannot be donated because the timed calls reuse the operands) already
    budgets ~8 GB of the 16 GB HBM — a 512K point OOMs.  The true 1M-lane
    BASELINE shape is measured by the striped driver (bench_orset_1m),
    which is also how that workload must actually execute on one chip."""
    c = 64 if tiny else 1024
    lanes = (128, 256, 512) if tiny else (1 << 16, 1 << 17, 1 << 18)
    for ln in lanes:
        per = _orset_union_rate(4, c, ln, tiny)
        if per is None:
            _emit(results, f"orset_sweep_{ln}_smoke", 1, "ok",
                  "interpret-mode (no TPU)")
            continue
        _emit(results, f"orset_unions_per_sec_{ln // 1024}k_lanes",
              ln / per, "replica-unions/s",
              f"C={c}, {ln} lanes ({per * 1e3:.1f} ms/union)",
              bytes_per_step=6 * c * ln * 4, sec_per_step=per,
              traffic_kind="compute")


def bench_orset_1m(results, tiny):
    """The OR-Set BASELINE config at its TRUE shape: C=1024 tags x 1M
    lanes, measured (not extrapolated).  A single pallas_call at this shape
    cannot run — the four operands alone are 4 x 4 GB = 16 GB, the v5e's
    entire HBM — so the driver is host-striped: 8 stripes x 128K lanes,
    each stripe's buffers freed before the next is built (the carry buffers
    inside each stripe's fori_loop are donated/reused by XLA).  The
    reported time for one 1M-lane union is the SUM of the per-stripe
    per-union times — i.e. exactly how this workload must execute on one
    chip — and the aggregate rate is 2^20 lanes / that sum."""
    stripes = 2 if tiny else 8
    c = 64 if tiny else 1024
    stripe_lanes = 256 if tiny else 1 << 17
    pers = []
    for s in range(stripes):
        per = _orset_union_rate(100 + s, c, stripe_lanes, tiny)
        if per is None:
            _emit(results, "orset_1m_striped_smoke", 1, "ok",
                  f"interpret-mode striped driver x{stripes} (no TPU)")
            return
        pers.append(per)
    total = sum(pers)
    n_lanes = stripes * stripe_lanes
    _emit(results, "orset_pallas_unions_per_sec_1m_striped",
          n_lanes / total, "replica-unions/s",
          f"MEASURED at BASELINE shape: C={c} x {n_lanes} lanes as "
          f"{stripes} x {stripe_lanes}-lane stripes; one full union = "
          f"{total * 1e3:.0f} ms (per-stripe {min(pers) * 1e3:.1f}-"
          f"{max(pers) * 1e3:.1f} ms); carry seeds donated on-chip",
          bytes_per_step=6 * c * n_lanes * 4, sec_per_step=total,
          traffic_kind="compute", dispatches=stripes)


def bench_orset_engines(results, tiny):
    """Three-arm set-union engine A/B (sort vs bucket vs bitmap) at one
    shape, arms INTERLEAVED and the bit-equality gate asserted per rep
    (standalone driver: benches/bench_orset.py --three-arm; engines:
    crdt_tpu/ops/union_engine.py).  Off-TPU the parity gate still runs —
    the rate rows need the chip."""
    import argparse as _argparse

    from benches import bench_orset as bo

    c = 64 if tiny else 1024
    ln = 128 if tiny else 1 << 17
    ns = _argparse.Namespace(tiny=tiny, capacity=c, lanes=ln, bank=2, k=8,
                             buckets=None, space=None, interpret=False)
    pers = bo.run_three_arm(ns)
    if pers is None:
        _emit(results, "orset_engine_ab_smoke", 1, "ok",
              "three-arm parity gate bit-identical (interpret mode, no TPU)")
        return
    base = pers["sort"]
    for name, per in pers.items():
        _emit(results, f"orset_union_{name}_unions_per_sec", ln / per,
              "replica-unions/s",
              f"engine arm '{name}' C={c} x {ln} lanes, interleaved A/B, "
              f"bit parity per rep, x{base / per:.2f} vs sort",
              bytes_per_step=6 * c * ln * 4, sec_per_step=per,
              traffic_kind="compute")


def bench_gossip_allreduce(results, tiny):
    """10K-replica swarm convergence: one step = tree-reduced join fixpoint +
    broadcast (what the reference needs many 1500 ms gossip rounds for)."""
    import jax
    import jax.numpy as jnp

    from crdt_tpu.ops import joins
    from crdt_tpu.models import gcounter

    r = 256 if tiny else 10_240
    bank_n, nodes = 4, 8
    ks = jax.random.split(jax.random.key(5), 2)
    state = jax.random.randint(ks[0], (r, nodes), 0, 1 << 20, dtype=jnp.int32)
    bank = jax.random.randint(ks[1], (bank_n, r, nodes), 0, 1 << 20,
                              dtype=jnp.int32)
    neutral = gcounter.zero(nodes)

    @partial(jax.jit, static_argnames="k")
    def chained(state, bank, k):
        def body(i, x):
            peer = jax.lax.dynamic_index_in_dim(bank, i % bank_n,
                                                keepdims=False)
            x = jnp.maximum(x, peer)  # fresh writes land on every replica
            top = joins.tree_reduce_join(
                lambda a, b: gcounter.GCounter(jnp.maximum(a.counts, b.counts)),
                gcounter.GCounter(x), neutral)
            return jnp.broadcast_to(top.counts[None], x.shape)

        return jax.lax.fori_loop(0, k, body, state).sum()

    ks_, kl = (4, 16) if tiny else (64, 512)
    per = _timed(lambda k: int(chained(state, bank, k)), ks_, kl,
                 min_diff=0 if tiny else MIN_DIFF_S)
    _emit(results, "gossip_allreduce_converges_per_sec", 1.0 / per,
          "converges/s",
          f"{r}-replica full convergence per step "
          f"({r / per:.3g} replica-merges/s equivalent)",
          bytes_per_step=_hbm_bytes_per_step(r * nodes * 4), sec_per_step=per)


# ---- driver -----------------------------------------------------------------

def bench_rseq_striped(results, tiny):
    """Full-depth RSeq ABOVE the monolithic kernel's VMEM ceiling: the
    capacity-striped engine at C=512 and C=1024 x D=6 (round-5; see
    benches/bench_rseq_striped.py for the standalone driver with the
    compiled-vs-oracle verify).  These capacities had NO viable compiled
    program before the striped path (kernel OOM; generic sort DNF)."""
    from benches import bench_rseq_striped as brs

    for c in (64,) if tiny else (512, 1024):
        for line in brs.bench_config(c, lanes=128 if tiny else 256):
            print(json.dumps(line), flush=True)
            results.append(line)


def bench_stripe_pipeline(results, tiny):
    """Serial vs double-buffered stripe execution A/B (the pipelined merge
    runtime's host-overlap arm; standalone driver with the staging cost
    models: benches/bench_pipeline.py)."""
    from benches import bench_pipeline as bp

    for line in bp.run_ab(tiny):
        print(json.dumps(line), flush=True)
        results.append(line)


ALL = {
    "gcounter_pair": bench_gcounter_pair,
    "pncounter_vmap": bench_pncounter_vmap,
    "pncounter_1m": bench_pncounter_1m,
    "lww_argmax": bench_lww_argmax,
    "lww_32m": bench_lww_32m,
    "lww_32m_packed": bench_lww_32m_packed,
    "orset_union": bench_orset_union,
    "orset_sweep": bench_orset_sweep,
    "orset_1m": bench_orset_1m,
    "orset_engines": bench_orset_engines,
    "stripe_pipeline": bench_stripe_pipeline,
    "rseq_striped": bench_rseq_striped,
    "gossip_allreduce": bench_gossip_allreduce,
}


def write_md(results, path):
    backend = None
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        pass
    lines = [
        "# BENCH_TABLE — full BASELINE suite results",
        "",
        f"Backend: `{backend}` · produced by `benches/bench_baseline.py` "
        "(difference-quotient timing; see module docstring).",
        "Headline metric (driver-run) lives in `bench.py`; reference "
        "publishes no numbers (BASELINE.md).",
        "",
        "Bandwidth columns (round-5): `HBM MB/step` is each config's "
        "per-step traffic model (`_hbm_bytes_per_step`: 3 planes when the "
        "loop carry exceeds VMEM, peer-plane-only when it is VMEM-resident; "
        "kernel rows count the pallas_call's 4-read/2-write planes), "
        "`eff TB/s` = that / measured step time, `% spec` is against the "
        "device's published HBM peak (crdt_tpu.obs.devtime table). "
        "`compute`-kind rows (the sorted-union kernel "
        "family) are VPU-bound — their low %-spec is expected; see PERF.md "
        "roofline. `—` = dispatch-bound config, no meaningful model.",
        "",
        "| metric | value | unit | HBM MB/step | eff TB/s | % spec | kind | notes |",
        "|---|---:|---|---:|---:|---:|---|---|",
    ]
    for r in results:
        v = r["value"]
        pretty = f"{v:,.1f}" if v < 1e6 else f"{v:.3e}"
        if "eff_tb_s" in r:
            pct = r.get("pct_hbm_spec")
            bw = (f"{r['hbm_mb_per_step']:,.1f} | {r['eff_tb_s']:.3f} | "
                  f"{'—' if pct is None else f'{pct:.1f}'} | "
                  f"{r['traffic_kind']}")
        else:
            bw = "— | — | — | —"
        lines.append(f"| {r['metric']} | {pretty} | {r['unit']} | {bw} | "
                     f"{r['note']} |")
    lines += [
        "",
        "Fused-kernel A/B tables (columnar Pallas vs generic XLA: the "
        "lex2 OpLog engine and the lexN RSeq engine) live in `PERF.md`; "
        "drivers: `benches/bench_oplog_columnar.py`, "
        "`benches/bench_rseq_columnar.py`.",
        "",
    ]
    path.write_text("\n".join(lines))


def _run_isolated(names, args):
    """Run each bench in its OWN subprocess and collect its JSON lines.

    The big-shape benches are sized to a large fraction of the chip's HBM
    (the 256K-lane sweep point and each 128K stripe of the 1M driver
    budget several GB of operands + loop carry); running them after the
    smaller configs in one process leaves enough residue (executable
    scratch, cached donation buffers) to trip RESOURCE_EXHAUSTED.  Process
    isolation gives every config a clean HBM; the persistent compile cache
    (crdt_tpu.utils.compile_cache) keeps the repeated Mosaic compiles to
    one each."""
    import subprocess

    from jax._src import xla_bridge

    # one process per chip: the children need the device, so this parent
    # must not hold it (it never initializes a backend before spawning),
    # and each child runs to exit before the next starts
    assert not xla_bridge.backends_are_initialized(), \
        "--isolate parent initialized a JAX backend before spawning"
    results = []
    for name in names:
        cmd = [sys.executable, str(pathlib.Path(__file__).resolve()),
               "--only", name]
        if args.tiny:
            cmd.append("--tiny")
        if args.cpu:
            cmd.append("--cpu")
        if args.lanes is not None:
            cmd += ["--lanes", str(args.lanes)]
        if args.capacity is not None:
            cmd += ["--capacity", str(args.capacity)]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            raise RuntimeError(f"bench {name} failed (rc={proc.returncode})")
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                print(line, flush=True)
                row = json.loads(line)
                # each child emits its own end-of-run snapshot; keep them
                # out of the aggregated result table (and BENCH_TABLE.md)
                if row.get("metric") != "obs_snapshot":
                    results.append(row)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke shapes")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--only", choices=sorted(ALL), default=None)
    ap.add_argument("--lanes", type=int, default=None,
                    help="orset_union replica count override")
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--write-md", action="store_true",
                    help="refresh BENCH_TABLE.md at the repo root")
    ap.add_argument("--isolate", action="store_true",
                    help="one subprocess per bench (clean HBM each; how the "
                         "full suite must run on a 16 GB chip)")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    if args.isolate:
        names = [args.only] if args.only else list(ALL)
        results = _run_isolated(names, args)
    else:
        from crdt_tpu.utils import compile_cache

        compile_cache.enable()
        results = []
        for name, fn in ALL.items():
            if args.only and name != args.only:
                continue
            if name == "orset_union":
                fn(results, args.tiny, lanes=args.lanes,
                   capacity=args.capacity)
            else:
                fn(results, args.tiny)
    if args.write_md:
        write_md(results, REPO / "BENCH_TABLE.md")
    # end-of-run registry snapshot: row count + step-time histogram summary,
    # one JSON line in the same shape as the result rows
    print(json.dumps({
        "metric": "obs_snapshot", "value": float(len(results)),
        "unit": "rows", "note": "end-of-run metrics snapshot",
        "obs": {k: round(v, 6) for k, v in OBS.snapshot().items()},
    }), flush=True)


if __name__ == "__main__":
    main()
