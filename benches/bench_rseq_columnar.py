"""Swarm RSeq merge/convergence: columnar lexN Pallas fast path vs the
generic row-major XLA path — the round-3 "put RSeq on the fused kernel"
A/B (VERDICT round 2, item 3).

RSeq carries the heaviest keys in the framework (4·D = 24 sorted columns,
crdt_tpu/models/rseq.py); the generic join pays a full O(n log²n) 24-key
sort per merge.  The columnar layout packs the keys into 3·D = 18 words
and rides the fused lexN bitonic-merge kernel
(crdt_tpu.ops.pallas_union.sorted_union_columnar_fused_lexn).

Two measurements, both at the verdict's C=1024 shape:

* pairwise batched merge: R independent lane merges per step (the
  gossip-round shape), chained in a fori_loop (difference quotient);
* full swarm convergence: every replica to the LUB (tree reduction).

The synthetic swarm is layout-faithful (per-lane sorted packed planes,
~40% fill from a shared element pool so cross-lane duplicate keys are
plentiful, tombstone flags that DIFFER between copies so the OR-on-punch
path is exercised); semantic parity with rseq.join is covered by
tests/test_rseq_columnar.py (interpret) and benches/hw_selftest.py
(compiled Mosaic).

Run on the chip (the default backend there); --cpu for smoke runs.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import time

import numpy as np
import jax
import jax.numpy as jnp

from crdt_tpu.models import rseq, rseq_columnar as rc
from crdt_tpu.utils.constants import SENTINEL, SENTINEL_PY

SEQ_BITS = 20


def make_swarm_planes(seed, c, r, depth=rseq.DEPTH):
    """A columnar RSeq swarm: lanes hold random subsets of a shared pool of
    2C lexicographically-sorted packed key rows."""
    g = 2 * c
    rng = np.random.default_rng(seed)
    nk = 3 * depth
    pool = rng.integers(0, 1 << 29, (nk, g), dtype=np.int32)
    pool[2] = np.arange(g, dtype=np.int32)  # level-0 identity: unique
    order = np.lexsort(pool[::-1])          # lexicographic by word 0..nk-1
    pool = pool[:, order]
    elem_pool = rng.integers(0, 1 << 20, g, dtype=np.int32)

    mask = jnp.asarray(rng.random((g, r)) < 0.4)
    keys = jnp.where(mask[None], jnp.asarray(pool)[:, :, None], SENTINEL_PY)
    elem = jnp.where(mask, jnp.asarray(elem_pool)[:, None], 0)
    # tombstones differ per lane: the duplicate copies the kernel punches
    # disagree, exercising the OR-combine rule on every merge
    removed = jnp.where(
        mask, jnp.asarray(rng.integers(0, 2, (g, r), dtype=np.int32)), 0
    )
    planes = jax.lax.sort(
        [keys[i] for i in range(nk)] + [elem, removed],
        dimension=0, num_keys=nk, is_stable=True,
    )
    return rc.ColumnarRSeq(
        keys=jnp.stack(planes[:nk], axis=0)[:, :c],
        elem=planes[nk][:c],
        removed=planes[nk + 1][:c],
        seq_bits=SEQ_BITS,
    )


@jax.jit
def chained_merge_columnar(a, bank, k):
    def body(i, s):
        j = i % bank.elem.shape[0]
        b = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False), bank
        )
        return rc.merge(s, b.replace(seq_bits=a.seq_bits))

    out = jax.lax.fori_loop(0, k, body, a)
    return out.keys[0].sum() + out.removed.sum()


@jax.jit
def chained_merge_rowmajor(a, bank, k):
    def body(i, s):
        j = i % bank.elem.shape[0]
        b = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False), bank
        )
        return jax.vmap(rseq.join)(s, b)

    out = jax.lax.fori_loop(0, k, body, a)
    return out.keys.sum() + out.removed.sum()


@jax.jit
def chained_converge_columnar(col, k):
    out = jax.lax.fori_loop(0, k, lambda i, s: rc.converge(s), col)
    return out.keys[0].sum() + out.removed.sum()


@jax.jit
def chained_converge_rowmajor(state, k):
    from crdt_tpu.ops import joins
    from crdt_tpu.parallel import swarm

    c, d = state.keys.shape[-2], state.keys.shape[-1] // 4
    neutral = rseq.empty(c, d)
    jb = joins.batched(rseq.join)

    def body(i, st):
        return swarm.converge(swarm.make(st), jb, neutral).state

    out = jax.lax.fori_loop(0, k, body, state)
    return out.keys.sum() + out.removed.sum()


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
    ap.add_argument("--depth", type=int, default=None,
                    help="path-key depth (default rseq.DEPTH=6; shallower "
                         "depths cut the kernel's plane count — the "
                         "C=1024 full-depth 20-plane monolith failed to "
                         "compile in round 5)")
    ap.add_argument("--merge-lanes", type=int, default=1024)
    ap.add_argument("--converge-replicas", type=int, default=512)
    ap.add_argument("--bank", type=int, default=2)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--skip-rowmajor", action="store_true")
    ap.add_argument("--stage", default="all",
                    choices=["all", "merge", "converge"])
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    c = args.capacity

    if args.stage in ("all", "merge"):
        lanes = args.merge_lanes
        d = args.depth or rseq.DEPTH
        a = make_swarm_planes(0, c, lanes, depth=d)
        bank = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[make_swarm_planes(1 + i, c, lanes, depth=d)
              for i in range(args.bank)],
        )
        print(f"compiling columnar lexN merge (C={c}, R={lanes}, "
              f"{a.keys.shape[0]}+2 planes)...", flush=True)
        per = timed(lambda k: chained_merge_columnar(a, bank, k),
                    args.k, 4 * args.k)
        print(f"columnar merge:   {per*1e3:8.2f} ms/round "
              f"({lanes/per/1e6:8.2f}M lane-merges/s @ C={c}, R={lanes})",
              flush=True)
        if not args.skip_rowmajor:
            a_rm = rc.unstack(a)
            bank_rm = jax.vmap(rc.unstack)(bank)
            print("compiling row-major merge...", flush=True)
            per_rm = timed(
                lambda k: chained_merge_rowmajor(a_rm, bank_rm, k),
                max(args.k // 4, 1), args.k,
            )
            print(f"row-major merge:  {per_rm*1e3:8.2f} ms/round "
                  f"({lanes/per_rm/1e6:8.2f}M lane-merges/s) "
                  f"-> speedup x{per_rm/per:.2f}", flush=True)

    if args.stage in ("all", "converge"):
        r = args.converge_replicas
        col = make_swarm_planes(99, c, r, depth=args.depth or rseq.DEPTH)
        print(f"compiling columnar lexN converge (R={r}, C={c})...",
              flush=True)
        per_c = timed(lambda k: chained_converge_columnar(col, k),
                      args.k, 4 * args.k)
        print(f"columnar converge:{per_c*1e3:8.2f} ms/converge "
              f"(R={r}, C={c})", flush=True)
        if not args.skip_rowmajor:
            state = rc.unstack(col)
            print("compiling row-major converge...", flush=True)
            per_cr = timed(
                lambda k: chained_converge_rowmajor(state, k),
                max(args.k // 4, 1), args.k,
            )
            print(f"row-major converge:{per_cr*1e3:7.2f} ms/converge "
                  f"-> speedup x{per_cr/per_c:.2f}", flush=True)


if __name__ == "__main__":
    main()
