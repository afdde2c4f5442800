"""LWW streaming-size roofline diagnosis (round 5).

The streaming-size LWW config (`bench_baseline.py --only lww_32m`)
measures well below
the 84-89% of HBM spec the G/PN counters sustain for the same
bank-of-peers loop shape on the same chip.  This script times candidate variants in
isolation (`--variant NAME`, one subprocess each) so the gap's cause is
measured, not argued.

Variants (all at R = 32M registers as (262144, 128) 2-D int32 planes,
bank of 4 peers, chained fori_loop difference-quotient timing; 32M keeps
every variant's loop carry decisively past the 128 MB physical VMEM —
at 16M the packed carry is exactly 128 MB and the measurement flip-flops
9x between VMEM-resident and spilled runs, landing at impossible
>100%-of-spec rates when resident):

  current   lww.join as shipped: lexicographic (ts, rid) mask, three
            jnp.where selects sharing it.
  maxes     control for the access pattern: the SAME nine plane
            streams (read self x3, read peer x3, write x3) but three
            independent jnp.maximum — no cross-plane mask dependency.
            If this matches the counters' %-spec, the gap is the join
            program; if it matches `current`, the gap is the 3-plane
            pattern itself.
  packed    2-plane layout: key = ts << 6 | rid packed order-preserving
            into one int32 plane (bench ts < 2^20, rid < 64, so the
            pack fits in 26 bits), payload separate; join = one compare
            + two selects.  Cuts the logical floor from 9 to 6 plane
            streams.

Each line reports eff_tb_s against ITS OWN logical floor (planes x
R x 4 B x 3 for read-self/read-peer/write), so %-spec is comparable
across variants.
"""
from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from benches.bench_baseline import _timed, device_info, peak_tb_s

R = 1 << 25
SHAPE = (R // 128, 128)
BANK_N = 4


def _rand(key, hi):
    return jax.random.randint(key, SHAPE, 0, hi, dtype=jnp.int32)


def _bank(key, hi):
    return jax.random.randint(key, (BANK_N,) + SHAPE, 0, hi,
                              dtype=jnp.int32)


def variant_current():
    from crdt_tpu.models import lww

    ks = jax.random.split(jax.random.key(3), 6)
    a = lww.LWWRegister(ts=_rand(ks[0], 1 << 20), rid=_rand(ks[1], 64),
                        payload=_rand(ks[2], 1 << 20))
    bank = lww.LWWRegister(ts=_bank(ks[3], 1 << 20), rid=_bank(ks[4], 64),
                           payload=_bank(ks[5], 1 << 20))

    @partial(jax.jit, static_argnames="k")
    def chained(a, bank, k):
        def body(i, x):
            peer = jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, i % BANK_N,
                                                       keepdims=False), bank)
            return lww.join(x, peer)

        out = jax.lax.fori_loop(0, k, body, a)
        return out.ts.sum() + out.payload.sum()

    return (lambda k: int(chained(a, bank, k))), 3  # planes


def variant_maxes():
    ks = jax.random.split(jax.random.key(4), 6)
    a = tuple(_rand(k, 1 << 20) for k in ks[:3])
    bank = tuple(_bank(k, 1 << 20) for k in ks[3:])

    @partial(jax.jit, static_argnames="k")
    def chained(a, bank, k):
        def body(i, x):
            peer = tuple(
                jax.lax.dynamic_index_in_dim(b, i % BANK_N, keepdims=False)
                for b in bank)
            return tuple(jnp.maximum(p, q) for p, q in zip(x, peer))

        out = jax.lax.fori_loop(0, k, body, a)
        return sum(p.sum() for p in out)

    return (lambda k: int(chained(a, bank, k))), 3


def variant_packed():
    ks = jax.random.split(jax.random.key(5), 4)
    key_a = _rand(ks[0], 1 << 26)
    pay_a = _rand(ks[1], 1 << 20)
    key_b = _bank(ks[2], 1 << 26)
    pay_b = _bank(ks[3], 1 << 20)

    @partial(jax.jit, static_argnames="k")
    def chained(key, pay, key_b, pay_b, k):
        def body(i, s):
            kx, px = s
            kp = jax.lax.dynamic_index_in_dim(key_b, i % BANK_N,
                                              keepdims=False)
            pp = jax.lax.dynamic_index_in_dim(pay_b, i % BANK_N,
                                              keepdims=False)
            m = kp > kx
            return jnp.where(m, kp, kx), jnp.where(m, pp, px)

        ko, po = jax.lax.fori_loop(0, k, body, (key, pay))
        return ko.sum() + po.sum()

    return (lambda k: int(chained(key_a, pay_a, key_b, pay_b, k))), 2


VARIANTS = {
    "current": variant_current,
    "maxes": variant_maxes,
    "packed": variant_packed,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=sorted(VARIANTS), required=True)
    args = ap.parse_args()
    fn, planes = VARIANTS[args.variant]()
    per = _timed(fn, 32, 256)
    floor = 3 * planes * R * 4  # read self + read peer + write, per plane
    eff = floor / per / 1e12
    peak = peak_tb_s()
    print(json.dumps({
        "variant": args.variant,
        "device": device_info(),
        "ms_per_step": round(per * 1e3, 3),
        "eff_tb_s": round(eff, 3),
        "pct_hbm_spec": None if peak is None else round(100 * eff / peak, 1),
        "merges_per_s": round(R / per, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
