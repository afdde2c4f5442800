"""OR-Set union benchmark: Pallas bitonic-merge kernel vs XLA sort fallback,
plus the three-arm engine A/B (sort vs bucket vs bitmap).

BASELINE config: 1M replicas x 1K elements, sorted-segment union.  Run on
the chip (the default backend there); prints a comparison table.  Timing
uses the same difference quotient as bench.py: K chained unions inside one
jit, so the fixed dispatch cost cancels between two K values.

Three-arm A/B (``--three-arm``, and the only thing ``--tiny`` runs): the
same logical per-lane sets are materialized in each engine's native layout
(sorted / bucketed / presence-bitmap) and the three chained drivers are
timed INTERLEAVED — every rep round-robins all arms at both K values so
clock drift and thermal state hit each arm equally.  After every rep a
fresh operand draw is pushed through all three boundary engines
(crdt_tpu.ops.union_engine.engine_*) and the outputs are asserted
bit-identical — the parity gate rides inside the timing loop, not beside
it.  Keys are strided-jittered over a dense universe of 32*C tags so one
draw is legal for all three layouts (unique per lane, balanced buckets,
bitmap at exact traffic parity: ceil(32C/32) = C words).
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from crdt_tpu.ops import pallas_union
from crdt_tpu.ops import sorted_union as su
from crdt_tpu.utils.constants import SENTINEL


def make_columns(key, c, lanes, fill, space=None):
    """Per-lane sorted unique packed tags with SENTINEL padding.

    With ``space`` set, the ``fill`` live rows are strided-jittered over
    ``[0, space)`` — one key per ``space // fill`` stratum — so every lane
    is strictly increasing and unique BY CONSTRUCTION and the same draw is
    legal for all three engine layouts (globally sorted, range-bucketed
    with balanced buckets, dense-universe bitmap)."""
    if space is None:
        ks = jax.random.randint(key, (c, lanes), 0, 1 << 30, dtype=jnp.int32)
        ks = jax.lax.sort(ks, dimension=0)
    else:
        stride = max(space // max(fill, 1), 1)
        jit_ = jax.random.randint(key, (c, lanes), 0, stride, dtype=jnp.int32)
        ks = jnp.arange(c, dtype=jnp.int32)[:, None] * stride + jit_
    mask = jnp.arange(c)[:, None] < fill
    keys = jnp.where(mask, ks, SENTINEL)
    vals = (ks & 1).astype(jnp.int32)
    return keys, vals


@partial(jax.jit, static_argnames=("k", "interpret"))
def chained_pallas(ka, va, bank_k, bank_v, k, interpret=False):
    c = ka.shape[0]

    def body(i, carry):
        kk, vv = carry
        j = i % bank_k.shape[0]
        kb = jax.lax.dynamic_index_in_dim(bank_k, j, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(bank_v, j, keepdims=False)
        ko, vo, _ = pallas_union.sorted_union_columnar(
            kk, vv, kb, vb, out_size=c, interpret=interpret
        )
        return ko, vo

    ko, vo = jax.lax.fori_loop(0, k, body, (ka, va))
    return ko.sum() + vo.sum()


@partial(jax.jit, static_argnames=("k",))
def chained_xla(ka, va, bank_k, bank_v, k):
    """Fallback: generic sorted_union vmapped over lanes (row-major)."""
    c = ka.shape[0]

    def one_union(kk, vv, kb, vb):
        keys, vals, _ = su.sorted_union((kk,), vv, (kb,), vb,
                                        combine=lambda x, y: x | y, out_size=c)
        return keys[0], vals

    def body(i, carry):
        kk, vv = carry
        j = i % bank_k.shape[0]
        kb = jax.lax.dynamic_index_in_dim(bank_k, j, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(bank_v, j, keepdims=False)
        ko, vo = jax.vmap(one_union, in_axes=1, out_axes=1)(kk, vv, kb, vb)
        return ko, vo

    ko, vo = jax.lax.fori_loop(0, k, body, (ka, va))
    return ko.sum() + vo.sum()


@partial(jax.jit, static_argnames=("k", "n_buckets", "interpret"))
def chained_bucket(ka, va, bank_k, bank_v, k, n_buckets, interpret=False):
    """Bucket-arm driver: operands and carry stay in the BUCKETED layout
    (out_bucket_rows=Wb keeps the carry at steady-state capacity, so every
    step is shape-stable and chainable)."""
    c = ka.shape[0]
    wb = c // n_buckets

    def body(i, carry):
        kk, vv = carry
        j = i % bank_k.shape[0]
        kb = jax.lax.dynamic_index_in_dim(bank_k, j, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(bank_v, j, keepdims=False)
        ko, vo, _, _ = pallas_union.bucketed_union_columnar(
            kk, vv, kb, vb, n_buckets, out_bucket_rows=wb,
            interpret=interpret)
        return ko, vo

    ko, vo = jax.lax.fori_loop(0, k, body, (ka, va))
    return ko.sum() + vo.sum()


@partial(jax.jit, static_argnames=("k",))
def chained_bitmap(pa, ra, bank_p, bank_r, k):
    """Bitmap-arm driver: union of presence planes is one bitwise OR."""

    def body(i, carry):
        p, r = carry
        j = i % bank_p.shape[0]
        pb = jax.lax.dynamic_index_in_dim(bank_p, j, keepdims=False)
        rb = jax.lax.dynamic_index_in_dim(bank_r, j, keepdims=False)
        return p | pb, r | rb

    p, r = jax.lax.fori_loop(0, k, body, (pa, ra))
    return p.sum() + r.sum()


def assert_three_arm_parity(rep, c, lanes, space, n_buckets, key_bits,
                            interpret):
    """One fresh operand draw through all three boundary engines; outputs
    must be bit-identical (keys, vals, n_unique) — the per-rep gate."""
    from crdt_tpu.ops import union_engine as ue

    kk = jax.random.split(jax.random.key(9000 + rep), 2)
    ka, va = make_columns(kk[0], c, lanes, c // 2, space=space)
    kb, vb = make_columns(kk[1], c, lanes, c // 2, space=space)
    k0, v0, n0 = ue.engine_sort(ka, va, kb, vb, c, interpret=interpret)
    arms = {
        "bucket": ue.engine_bucket(ka, va, kb, vb, c, interpret=interpret,
                                   n_buckets=n_buckets, key_bits=key_bits),
        "bitmap": ue.engine_bitmap(ka, va, kb, vb, c, universe=space),
    }
    for name, (k1, v1, n1) in arms.items():
        ok = (bool(jnp.all(k0 == k1)) and bool(jnp.all(v0 == v1))
              and bool(jnp.all(n0 == n1)))
        assert ok, f"rep {rep}: {name} engine diverged from sort (bit parity)"


def timed_interleaved(fns, k_small, k_large, reps=3, per_rep=None):
    """Per-arm difference quotient with the arms round-robined inside each
    rep (every arm sees the same drift/thermal state); ``per_rep`` runs
    after each rep — the parity gate."""
    best = {n: {k_small: float("inf"), k_large: float("inf")} for n in fns}
    for fn in fns.values():  # compile + warm both K values
        int(fn(k_small))
        int(fn(k_large))
    for rep in range(reps):
        for k in (k_small, k_large):
            for n, fn in fns.items():
                t0 = time.perf_counter()
                _ = int(fn(k))
                best[n][k] = min(best[n][k], time.perf_counter() - t0)
        if per_rep is not None:
            per_rep(rep)
    return {n: (b[k_large] - b[k_small]) / (k_large - k_small)
            for n, b in best.items()}


def run_three_arm(args):
    """Interleaved sort/bucket/bitmap A/B at one shape, parity per rep.

    In ``--tiny`` (CI) mode the chained loops would be pathologically slow
    under interpret-pallas, so the gate runs the parity reps alone (which
    still push every engine — including the bucketed Pallas kernel in
    interpret mode — through real unions) and skips the rate table."""
    from crdt_tpu.ops import union_engine as ue

    c = 64 if args.tiny else args.capacity
    lanes = 128 if args.tiny else args.lanes
    n_buckets = args.buckets or max(2, c // 16)
    space = args.space or 32 * c  # bitmap traffic-parity bound: U = 32·C
    key_bits = max(space - 1, 1).bit_length()
    interpret = args.interpret or jax.default_backend() != "tpu"
    reps = 3

    plan = ue.plan_union(c, universe=space, key_bits=key_bits)
    print(f"three-arm A/B: C={c} lanes={lanes} buckets={n_buckets} "
          f"universe={space} (auto-dispatch would pick: {plan.path})")

    if args.tiny or interpret:
        for rep in range(reps):
            assert_three_arm_parity(rep, c, lanes, space, n_buckets,
                                    key_bits, interpret=True)
        # exercise the bucketed Pallas kernel arm itself (engine_bucket's
        # kernel path), not just the XLA twin
        kk = jax.random.split(jax.random.key(42), 2)
        ka, va = make_columns(kk[0], c, lanes, c // 2, space=space)
        kb, vb = make_columns(kk[1], c, lanes, c // 2, space=space)
        bka, bva, da = ue.sorted_to_bucketed(ka, va, n_buckets, key_bits)
        bkb, bvb, db = ue.sorted_to_bucketed(kb, vb, n_buckets, key_bits)
        assert int(da.max()) == 0 and int(db.max()) == 0
        wb = c // n_buckets
        ko, vo, nu, _ = pallas_union.bucketed_union_columnar(
            bka, bva, bkb, bvb, n_buckets, out_bucket_rows=2 * wb,
            interpret=True)
        kx, vx, nx, _ = pallas_union.bucketed_union_columnar_xla(
            bka, bva, bkb, bvb, n_buckets, out_bucket_rows=2 * wb)
        assert bool(jnp.all(ko == kx)) and bool(jnp.all(vo == vx))
        assert bool(jnp.all(nu == nx))
        print(f"three-arm parity OK: {reps} reps bit-identical "
              f"(sort == bucket == bitmap), bucketed kernel == XLA twin")
        return None

    # full mode on the chip: native-layout operands + bank per arm
    keys = jax.random.split(jax.random.key(7), args.bank + 1)
    ka, va = make_columns(keys[0], c, lanes, c // 2, space=space)
    bank = [make_columns(k2, c, lanes, c // 2, space=space)
            for k2 in keys[1:]]
    bank_k = jnp.stack([b[0] for b in bank])
    bank_v = jnp.stack([b[1] for b in bank])

    bka, bva, da = ue.sorted_to_bucketed(ka, va, n_buckets, key_bits)
    assert int(da.max()) == 0, "strided draw must bucket cleanly"
    bbank = [ue.sorted_to_bucketed(k2, v2, n_buckets, key_bits)[:2]
             for k2, v2 in bank]
    bbank_k = jnp.stack([b[0] for b in bbank])
    bbank_v = jnp.stack([b[1] for b in bbank])

    pa, ra = ue.sorted_to_bitmap(ka, va, space)
    pbank = [ue.sorted_to_bitmap(k2, v2, space) for k2, v2 in bank]
    bank_p = jnp.stack([b[0] for b in pbank])
    bank_r = jnp.stack([b[1] for b in pbank])

    fns = {
        "sort": lambda k: chained_pallas(ka, va, bank_k, bank_v, k, False),
        "bucket": lambda k: chained_bucket(bka, bva, bbank_k, bbank_v, k,
                                           n_buckets, False),
        "bitmap": lambda k: chained_bitmap(pa, ra, bank_p, bank_r, k),
    }
    pers = timed_interleaved(
        fns, args.k, 4 * args.k, reps=reps,
        per_rep=lambda rep: assert_three_arm_parity(
            rep, c, lanes, space, n_buckets, key_bits, interpret=False))
    base = pers["sort"]
    for name, per in pers.items():
        print(f"{name:>7}: {per*1e3:8.2f} ms/union-step "
              f"({lanes/per/1e6:8.1f}M replica-unions/s)  "
              f"x{base/per:.2f} vs sort")
    print(f"parity: {reps} reps bit-identical across all three engines")
    return pers


def timed(fn, k_small, k_large, reps=3):
    def run(k):
        _ = int(fn(k))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _ = int(fn(k))
            best = min(best, time.perf_counter() - t0)
        return best

    t1, t2 = run(k_small), run(k_large)
    return (t2 - t1) / (k_large - k_small)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--lanes", type=int, default=1 << 20,
                    help="replicas (BASELINE: 1M)")
    ap.add_argument("--bank", type=int, default=4)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--skip-xla", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend (smoke runs)")
    ap.add_argument("--three-arm", action="store_true",
                    help="interleaved sort/bucket/bitmap A/B with the "
                         "per-rep bit-equality gate")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: three-arm parity gate at C=64, 128 "
                         "lanes (implies --three-arm, interpret kernels)")
    ap.add_argument("--buckets", type=int, default=None,
                    help="bucket count for the bucket arm "
                         "(default: the dispatcher's max(2, C//16))")
    ap.add_argument("--space", type=int, default=None,
                    help="tag universe for the dense draw "
                         "(default 32*C: the bitmap traffic-parity bound)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    if args.tiny or args.three_arm:
        run_three_arm(args)
        return

    c, lanes = args.capacity, args.lanes
    keys = jax.random.split(jax.random.key(0), args.bank + 1)
    ka, va = make_columns(keys[0], c, lanes, fill=c // 2)
    bank = [make_columns(k2, c, lanes, fill=c // 2) for k2 in keys[1:]]
    bank_k = jnp.stack([b[0] for b in bank])
    bank_v = jnp.stack([b[1] for b in bank])

    if args.interpret:
        # smoke mode: interpret-pallas inside fori_loop is pathologically
        # slow; just run a couple of eager unions to prove the path works
        ko, vo, _ = pallas_union.sorted_union_columnar(
            ka, va, bank_k[0], bank_v[0], out_size=c, interpret=True
        )
        jax.block_until_ready((ko, vo))
        print(f"interpret smoke OK: union C={c} lanes={lanes}")
        return

    per = timed(
        lambda k: chained_pallas(ka, va, bank_k, bank_v, k, args.interpret),
        args.k, 4 * args.k,
    )
    rate = lanes / per
    print(f"pallas bitonic union: {per*1e3:.2f} ms/union-step "
          f"({rate/1e6:.1f}M replica-unions/s @ C={c})")

    if not args.skip_xla:
        per_x = timed(lambda k: chained_xla(ka, va, bank_k, bank_v, k),
                      max(args.k // 4, 2), args.k)
        print(f"xla sort fallback:    {per_x*1e3:.2f} ms/union-step "
              f"({lanes/per_x/1e6:.1f}M replica-unions/s) "
              f"-> speedup x{per_x/per:.2f}")


if __name__ == "__main__":
    main()
