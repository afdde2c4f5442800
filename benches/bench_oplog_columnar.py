"""Swarm OpLog merge/convergence: columnar Pallas fast path vs the generic
row-major XLA path — the round-2 "route the flagship merge through the
fused kernel" A/B (VERDICT round 1, item 2).

Two measurements, both at the verdict's C=1024 shape:

* pairwise batched merge: R independent lane merges per step (the gossip-
  round shape), chained in a fori_loop (difference quotient) like
  bench_orset.py;
* full swarm convergence: every replica to the LUB (tree reduction), the
  shape swarm.converge runs.

Run on the chip (the default backend there); --cpu for smoke runs.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import time
from functools import partial

import jax
import jax.numpy as jnp

from crdt_tpu.models import oplog, oplog_columnar as oc
from crdt_tpu.ops import joins
from crdt_tpu.parallel import swarm

BITS = (8, 16, 7)


def make_swarm_planes(key, c, r, n_writers=256, n_keys=62):
    """A columnar swarm whose lanes hold random subsets of a shared op pool
    (cross-lane duplicates are plentiful, like a mid-gossip swarm)."""
    g = 2 * c
    gi = jnp.arange(g, dtype=jnp.int32)
    ts = gi // 3                      # deliberate ts collisions
    rid = gi % n_writers
    seq = gi                          # globally unique identity
    kcol = (gi * 40503) % n_keys
    hi_pool = ts
    lo_pool = oc.pack_id(rid, seq, kcol, BITS)
    val_pool = (gi % 41) - 20
    pay_pool = (gi % 1000) | ((gi % 2) << 31)

    mask = jax.random.bernoulli(key, 0.4, (g, r))
    from crdt_tpu.utils.constants import SENTINEL

    hi = jnp.where(mask, hi_pool[:, None], SENTINEL)
    lo = jnp.where(mask, lo_pool[:, None], SENTINEL)
    val = jnp.where(mask, val_pool[:, None], 0)
    pay = jnp.where(mask, pay_pool[:, None], 0)
    # sort each LANE (axis 0 = the per-replica log), not the default last
    # axis — the kernel's per-lane sorted-ascending precondition
    hi, lo, val, pay = jax.lax.sort(
        [hi, lo, val, pay], dimension=0, num_keys=2, is_stable=True
    )
    return oc.ColumnarOpLog(
        hi=hi[:c], lo=lo[:c], val=val[:c], pay=pay[:c], bits=BITS
    )


# k is a TRACED loop bound (lax.fori_loop lowers it to a while loop): one
# compile serves every k.


@jax.jit
def chained_merge_columnar(a, bank, k):
    def body(i, s):
        j = i % bank.hi.shape[0]
        b = jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False), bank)
        return oc.merge(s, b.replace(bits=a.bits))

    out = jax.lax.fori_loop(0, k, body, a)
    return out.hi.sum() + out.val.sum()


@jax.jit
def chained_merge_rowmajor(a, bank, k):
    def body(i, s):
        j = i % bank.ts.shape[0]
        b = jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False), bank)
        return jax.vmap(oplog.merge)(s, b)

    out = jax.lax.fori_loop(0, k, body, a)
    return out.ts.sum() + out.val.sum()


@jax.jit
def chained_converge_columnar(col, k):
    # convergence is a fixpoint, but the bitonic network is data-oblivious:
    # every chained converge costs the same, so chaining is fair timing
    out = jax.lax.fori_loop(0, k, lambda i, s: oc.converge(s), col)
    return out.hi.sum() + out.val.sum()


@partial(jax.jit, static_argnames="c")
def chained_converge_rowmajor(state, k, c):
    neutral = oplog.empty(c)
    jb = joins.batched(oplog.merge)

    def body(i, st):
        return swarm.converge(swarm.make(st), jb, neutral).state

    out = jax.lax.fori_loop(0, k, body, state)
    return out.ts.sum() + out.val.sum()


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
    ap.add_argument("--merge-lanes", type=int, default=4096)
    ap.add_argument("--converge-replicas", type=int, default=1024)
    ap.add_argument("--bank", type=int, default=4)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--skip-rowmajor", action="store_true")
    ap.add_argument("--stage", default="all",
                    choices=["all", "merge", "converge"])
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    c = args.capacity
    keys = jax.random.split(jax.random.key(0), args.bank + 2)

    if args.stage in ("all", "merge"):
        # --- pairwise batched merge ---------------------------------------
        lanes = args.merge_lanes
        a = make_swarm_planes(keys[0], c, lanes)
        bank = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[make_swarm_planes(k2, c, lanes) for k2 in keys[1 : args.bank + 1]],
        )
        print(f"compiling columnar merge (C={c}, R={lanes})...", flush=True)
        per = timed(lambda k: chained_merge_columnar(a, bank, k), args.k, 4 * args.k)
        print(f"columnar merge:   {per*1e3:8.2f} ms/round "
              f"({lanes/per/1e6:8.1f}M lane-merges/s @ C={c}, R={lanes})",
              flush=True)
        if not args.skip_rowmajor:
            a_rm = oc.unstack(a)
            bank_rm = jax.vmap(oc.unstack)(bank)
            print("compiling row-major merge...", flush=True)
            per_rm = timed(
                lambda k: chained_merge_rowmajor(a_rm, bank_rm, k),
                max(args.k // 4, 2), args.k,
            )
            print(f"row-major merge:  {per_rm*1e3:8.2f} ms/round "
                  f"({lanes/per_rm/1e6:8.1f}M lane-merges/s) "
                  f"-> speedup x{per_rm/per:.2f}", flush=True)

    if args.stage in ("all", "converge"):
        # --- full swarm convergence ---------------------------------------
        r = args.converge_replicas
        col = make_swarm_planes(keys[-1], c, r)
        print(f"compiling columnar converge (R={r}, C={c})...", flush=True)
        per_c = timed(lambda k: chained_converge_columnar(col, k), args.k, 4 * args.k)
        print(f"columnar converge:{per_c*1e3:8.2f} ms/converge "
              f"(R={r}, C={c})", flush=True)
        if not args.skip_rowmajor:
            state = oc.unstack(col)
            print("compiling row-major converge...", flush=True)
            per_cr = timed(
                lambda k: chained_converge_rowmajor(state, k, c),
                max(args.k // 4, 2), args.k,
            )
            print(f"row-major converge:{per_cr*1e3:7.2f} ms/converge "
                  f"-> speedup x{per_cr/per_c:.2f}", flush=True)


if __name__ == "__main__":
    main()
