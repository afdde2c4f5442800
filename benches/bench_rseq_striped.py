"""Full-depth RSeq above the monolithic kernel's VMEM ceiling: the
capacity-striped path measured on the chip (round-4 verdict task 2).

The fused lexN kernel OOMs VMEM at C=512 x D=6 ("129.60M of 128.00M",
PERF.md) and the generic 26-operand sort DNFs its TPU compile — so before
this path existed, a full-depth sequence swarm was hard-capped at C=256
rows/lane.  The striped union (pallas_union.sorted_union_columnar_striped_lexn)
serves C=512..4096+ through C<=256 merge-only stripe calls plus one XLA
dedup/compaction epilogue, and the engine auto-selects it
(sorted_union_columnar_lexn_auto) whenever the monolith would not fit.

Per config this driver:
  1. verifies the compiled striped path against the interpret-mode fused
     oracle at small lanes (the hw_selftest discipline: Mosaic lowering
     breaks must fail the bench, not ship numbers);
  2. measures one swarm merge round (bank-of-peers fori_loop, difference-
     quotient timing) and one full swarm convergence (lane-halving tree).

Usage:
  python benches/bench_rseq_striped.py                # C=512 and C=1024
  python benches/bench_rseq_striped.py --configs 512  # one config
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from functools import partial

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from benches.bench_rseq_columnar import make_swarm_planes
from crdt_tpu.models import rseq_columnar as rc
from crdt_tpu.ops import pallas_union as pu

from benches.bench_baseline import _timed  # noqa: E402  (warns + clamps
# when the difference quotient never clears the dispatch-noise floor — the
# local near-duplicate this module used to carry returned silent noise)

DEPTH = 6


def verify(c):
    """Compiled striped vs interpret-mode striped AND fused oracles at
    small lanes.  (The fused monolith cannot run at these capacities on
    the chip — that inability is this path's reason to exist — so the
    oracle runs interpret-mode on the same inputs.)"""
    col = make_swarm_planes(7, c, 2 * pu.LANES, depth=DEPTH)
    nk = col.keys.shape[0]
    a = jax.tree.map(lambda x: x[..., : pu.LANES], col)
    b = jax.tree.map(lambda x: x[..., pu.LANES :], col)
    ka = tuple(a.keys[i] for i in range(nk))
    kb = tuple(b.keys[i] for i in range(nk))
    va, vb = (a.elem, a.removed), (b.elem, b.removed)
    on_tpu = jax.default_backend() == "tpu"
    got = pu.sorted_union_columnar_striped_lexn(
        ka, va, kb, vb, out_size=c, interpret=not on_tpu
    )
    want = pu.sorted_union_columnar_fused_lexn(
        ka, va, kb, vb, out_size=c, interpret=True
    )
    for g, w in zip(got[0] + got[1] + (got[2],),
                    want[0] + want[1] + (want[2],)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    print(f"# verify C={c} x D={DEPTH}: striped (compiled="
          f"{on_tpu}) == fused interpret oracle", file=sys.stderr)


def bench_config(c, lanes=256, bank_n=4):
    interpret = jax.default_backend() != "tpu"
    col = make_swarm_planes(1, c, lanes, depth=DEPTH)
    bank = [make_swarm_planes(10 + i, c, lanes, depth=DEPTH)
            for i in range(bank_n)]
    bank_k = jnp.stack([b.keys for b in bank])
    bank_e = jnp.stack([b.elem for b in bank])
    bank_r = jnp.stack([b.removed for b in bank])

    @partial(jax.jit, static_argnames="k")
    def chained(col, bank_k, bank_e, bank_r, k):
        def body(i, x):
            j = i % bank_n
            peer = rc.ColumnarRSeq(
                keys=jax.lax.dynamic_index_in_dim(bank_k, j, keepdims=False),
                elem=jax.lax.dynamic_index_in_dim(bank_e, j, keepdims=False),
                removed=jax.lax.dynamic_index_in_dim(bank_r, j,
                                                     keepdims=False),
                seq_bits=col.seq_bits,
            )
            return rc.merge(x, peer, interpret=interpret)

        out = jax.lax.fori_loop(0, k, body, col)
        return out.keys.sum()

    results = []
    if interpret:
        out = rc.merge(col, bank[0], interpret=True)
        jax.block_until_ready(out.keys)
        results.append({
            "metric": f"rseq_striped_smoke_c{c}", "value": 1, "unit": "ok",
            "vs_baseline": None,
            "note": f"interpret-mode striped merge C={c} D={DEPTH} (no TPU)",
        })
        return results
    per = _timed(lambda k: int(chained(col, bank_k, bank_e, bank_r, k)),
                 4, 16)
    results.append({
        "metric": f"rseq_striped_swarm_round_c{c}",
        "value": round(lanes / per, 1), "unit": "lane-merges/s",
        "vs_baseline": None,
        "note": f"full-depth D={DEPTH} striped swarm merge, C={c} x "
                f"{lanes} lanes ({per * 1e3:.2f} ms/round)",
    })

    # Chained difference-quotient, same discipline as every other number
    # here: a single blocking converge pays a fixed dispatch + sync cost,
    # which inflated the first committed measurement of a ~10-25 ms
    # device-side program.  Chaining k converges in one fori_loop cancels
    # that fixed cost out of the quotient; the tree network is
    # data-independent, so re-converging the already-converged carry does
    # identical device work each step.
    @partial(jax.jit, static_argnames="k")
    def conv_chain(col, k):
        out = jax.lax.fori_loop(
            0, k, lambda i, s: rc.converge(s, interpret=interpret), col
        )
        return out.keys.sum()

    per = _timed(lambda k: int(conv_chain(col, k)), 2, 8)
    results.append({
        "metric": f"rseq_striped_converge_c{c}",
        "value": round(per * 1e3, 2), "unit": "ms/converge",
        "vs_baseline": None,
        "note": f"full swarm convergence ({lanes} lanes -> LUB), "
                f"C={c} x D={DEPTH} striped engine",
    })
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", type=int, nargs="*", default=[512, 1024])
    ap.add_argument("--lanes", type=int, default=256)
    args = ap.parse_args()
    from benches.bench_baseline import _enable_compile_cache

    _enable_compile_cache()
    for c in args.configs:
        verify(c)
        for line in bench_config(c, lanes=args.lanes):
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
