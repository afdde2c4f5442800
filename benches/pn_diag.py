"""PN-Counter 1M roofline diagnosis (round-5 task #1).

The judge measured the PN 1M config at 2.688e8 replica-merges/s = 3.72 ms
per step over >=1.5 GB of plane traffic ~= 0.40 TB/s effective, 5x below
the 2.2 TB/s the G-Counter headline sustains on the same chip.  This
script times candidate program variants in isolation, one per subprocess
(`--variant NAME`), so the winner (and the loser's cause) is measured,
not argued.

Variants:
  current   the bench_baseline.py program as shipped: bank (4, 2, R, 64),
            one dynamic_index_in_dim materializing a (2, R, 64) peer,
            then peer[0]/peer[1] static slices into two maximums.
  split     separate pos/neg banks (4, R, 64): each dynamic slice feeds
            exactly one maximum -> fusible producer, no (2,R,64) temp.
  fused     ONE plane: state (R, 128) with pos in lanes 0-63, neg in
            64-127; bank (4, R, 128); one maximum.  The PN join is an
            elementwise max on both planes at once -- the layout makes
            that literally one array op, and the 128-lane minor dim is
            exactly the TPU vector width (a 64-lane minor pads to 128
            in VMEM tiles).
  control   raw achievable rate at the same logical bytes: G-Counter
            style single (2R, 64) plane, bank of 4 -- the same program
            shape that measures 2.2 TB/s at (1M, 8).

Each prints one JSON line {variant, ms_per_step, eff_tb_s, merges_per_s}
where eff_tb_s uses the LOGICAL traffic floor 3 * 2 * R * 64 * 4 B
(read self + read peer + write result, both planes).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

R = 1 << 20
NODES = 64
BANK_N = 4
MIN_DIFF_S = 0.02
# logical traffic floor per step: read self + read peer + write, 2 planes
BYTES_PER_STEP = 3 * 2 * R * NODES * 4


def timed(fn, k_small=64, k_large=512, reps=5):
    def run(k):
        fn(k)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(k)
            best = min(best, time.perf_counter() - t0)
        return best

    for _ in range(4):
        t1, t2 = run(k_small), run(k_large)
        if t2 - t1 >= MIN_DIFF_S:
            break
        k_small, k_large = k_small * 4, k_large * 4
    return (t2 - t1) / (k_large - k_small)


def v_current():
    ks = jax.random.split(jax.random.key(2), 3)
    pos = jax.random.randint(ks[0], (R, NODES), 0, 1 << 20, dtype=jnp.int32)
    neg = jax.random.randint(ks[1], (R, NODES), 0, 1 << 20, dtype=jnp.int32)
    bank = jax.random.randint(ks[2], (BANK_N, 2, R, NODES), 0, 1 << 20,
                              dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(pos, neg, bank, k):
        def body(i, x):
            p, n = x
            peer = jax.lax.dynamic_index_in_dim(bank, i % BANK_N,
                                                keepdims=False)
            return (jnp.maximum(p, peer[0]), jnp.maximum(n, peer[1]))

        p, n = jax.lax.fori_loop(0, k, body, (pos, neg))
        return p.sum() - n.sum()

    return timed(lambda k: int(chained(pos, neg, bank, k)))


def v_split():
    ks = jax.random.split(jax.random.key(2), 4)
    pos = jax.random.randint(ks[0], (R, NODES), 0, 1 << 20, dtype=jnp.int32)
    neg = jax.random.randint(ks[1], (R, NODES), 0, 1 << 20, dtype=jnp.int32)
    bank_p = jax.random.randint(ks[2], (BANK_N, R, NODES), 0, 1 << 20,
                                dtype=jnp.int32)
    bank_n = jax.random.randint(ks[3], (BANK_N, R, NODES), 0, 1 << 20,
                                dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(pos, neg, bank_p, bank_n, k):
        def body(i, x):
            p, n = x
            j = i % BANK_N
            pp = jax.lax.dynamic_index_in_dim(bank_p, j, keepdims=False)
            pn = jax.lax.dynamic_index_in_dim(bank_n, j, keepdims=False)
            return (jnp.maximum(p, pp), jnp.maximum(n, pn))

        p, n = jax.lax.fori_loop(0, k, body, (pos, neg))
        return p.sum() - n.sum()

    return timed(lambda k: int(chained(pos, neg, bank_p, bank_n, k)))


def v_fused():
    ks = jax.random.split(jax.random.key(2), 2)
    state = jax.random.randint(ks[0], (R, 2 * NODES), 0, 1 << 20,
                               dtype=jnp.int32)
    bank = jax.random.randint(ks[1], (BANK_N, R, 2 * NODES), 0, 1 << 20,
                              dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(state, bank, k):
        def body(i, x):
            peer = jax.lax.dynamic_index_in_dim(bank, i % BANK_N,
                                                keepdims=False)
            return jnp.maximum(x, peer)

        out = jax.lax.fori_loop(0, k, body, state)
        return out[:, :NODES].sum() - out[:, NODES:].sum()

    return timed(lambda k: int(chained(state, bank, k)))


def v_control():
    ks = jax.random.split(jax.random.key(2), 2)
    state = jax.random.randint(ks[0], (2 * R, NODES), 0, 1 << 20,
                               dtype=jnp.int32)
    bank = jax.random.randint(ks[1], (BANK_N, 2 * R, NODES), 0, 1 << 20,
                              dtype=jnp.int32)

    @partial(jax.jit, static_argnames="k")
    def chained(state, bank, k):
        def body(i, x):
            peer = jax.lax.dynamic_index_in_dim(bank, i % BANK_N,
                                                keepdims=False)
            return jnp.maximum(x, peer)

        return jax.lax.fori_loop(0, k, body, state).sum()

    return timed(lambda k: int(chained(state, bank, k)))


VARIANTS = {"current": v_current, "split": v_split, "fused": v_fused,
            "control": v_control}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=sorted(VARIANTS), required=False)
    args = ap.parse_args()
    if args.variant:
        per = VARIANTS[args.variant]()
        print(json.dumps({
            "variant": args.variant,
            "ms_per_step": round(per * 1e3, 3),
            "eff_tb_s": round(BYTES_PER_STEP / per / 1e12, 3),
            "merges_per_s": round(R / per, 1),
        }), flush=True)
        return
    # driver: one subprocess per variant for a clean HBM each — run one
    # at a time, from a parent that never initializes a backend (a chip
    # belongs to one process)
    import subprocess

    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized()
    for name in ("current", "split", "fused", "control"):
        proc = subprocess.run(
            [sys.executable, __file__, "--variant", name],
            capture_output=True, text=True)
        sys.stderr.write(proc.stderr)
        print(proc.stdout, end="", flush=True)
        if proc.returncode != 0:
            print(f"# {name} FAILED rc={proc.returncode}", flush=True)


if __name__ == "__main__":
    main()
