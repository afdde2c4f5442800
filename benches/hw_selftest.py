"""Hardware self-test: run the kernel correctness oracles COMPILED on the
real chip (CI runs them interpret-mode on CPU only — Mosaic lowering
differences are exactly what interpret mode cannot catch; the workarounds
in ops/pallas_union.py exist because of such differences).

Checks, each against an independent oracle on the same data (the generic
XLA sorted_union for most; check 6's oracle is the fused monolith in
interpret mode, itself pinned to the generic path by checks 1-5 and the
CI suite):

  1. OR-combine fused union (sorted_union_columnar) at C=64 and C=1024;
  2. lex2 keep-first fused union (the OpLog path) incl. n_unique;
  3. columnar OpLog merge/converge vs the vmapped row-major path;
  4. sharded_converge on a 1-device mesh (compiled Mosaic under shard_map);
  5. lexN (18-key-word) fused union: columnar RSeq merge vs the vmapped
     generic 24-column join, incl. the tombstone OR-on-punch rule;
  6. capacity-striped union with the compact-kernel epilogue forced
     (the round-5 compiled epilogue) vs the fused monolith oracle;
  7. GC-aware columnar RSeq join (rseq_engine) vs the generic tomb_gc
     join, with diverged per-lane floors;
  8. sharded GC-aware converge under shard_map.

Run after ANY kernel change:  python benches/hw_selftest.py
Exit code 0 = all green.  About a minute of compiles (round 5).

`bench.py` runs checks 1(C=64)+2-6 (`run(full=False)` — every fused path,
small shapes) before producing its headline JSON whenever the backend is a
real accelerator, logging to stderr, so a Mosaic lowering regression in
ANY fused path fails the bench before a number exists.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from crdt_tpu.models import oplog, oplog_columnar as oc
from crdt_tpu.ops import pallas_union, sorted_union as su
from crdt_tpu.parallel import mesh as mesh_lib
from crdt_tpu.utils.constants import SENTINEL_PY


_log = print  # rebound by run() so library callers can keep stdout clean


def _cols(rng, c, lanes, fill_max):
    keys = np.full((c, lanes), SENTINEL_PY, np.int32)
    vals = np.zeros((c, lanes), np.int32)
    for j in range(lanes):
        n = int(rng.integers(0, c + 1))
        ks = np.sort(rng.choice(fill_max, size=n, replace=False))
        keys[:n, j] = ks
        vals[:n, j] = rng.integers(0, 8, n)
    return jnp.asarray(keys), jnp.asarray(vals)


def check_or_kernel(c):
    rng = np.random.default_rng(c)
    lanes = 128
    ka, va = _cols(rng, c, lanes, fill_max=4 * c)
    kb, vb = _cols(rng, c, lanes, fill_max=4 * c)
    ko, vo, nu = pallas_union.sorted_union_columnar(ka, va, kb, vb, out_size=c)
    for j in range(0, lanes, 31):
        keys, vals, n = su.sorted_union(
            (ka[:, j],), va[:, j], (kb[:, j],), vb[:, j],
            combine=lambda x, y: x | y, out_size=c,
        )
        np.testing.assert_array_equal(np.asarray(keys[0]), np.asarray(ko[:, j]))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(vo[:, j]))
        assert int(n) == int(nu[j])
    _log(f"  OR-combine union C={c}: OK")


def check_lex2_kernel():
    rng = np.random.default_rng(7)
    c, lanes = 64, 128
    # (hi, lo) key pairs sorted lexicographically; values key-determined
    # so the keep-first duplicate rule is well-posed
    hi = np.full((c, lanes), SENTINEL_PY, np.int32)
    lo = np.full((c, lanes), SENTINEL_PY, np.int32)
    v1 = np.zeros((c, lanes), np.int32)
    v2 = np.zeros((c, lanes), np.int32)
    hi2, lo2 = hi.copy(), lo.copy()
    w1, w2 = v1.copy(), v2.copy()
    for j in range(lanes):
        for dst_h, dst_l, dv1, dv2 in ((hi, lo, v1, v2), (hi2, lo2, w1, w2)):
            n = int(rng.integers(0, c + 1))
            pairs = sorted({(int(rng.integers(0, 40)), int(rng.integers(0, 4)))
                            for _ in range(n)})
            for r, (h, l) in enumerate(pairs):
                dst_h[r, j], dst_l[r, j] = h, l
                dv1[r, j] = h * 131 + l * 7 + 1
                dv2[r, j] = h * 17 + l + 1
    args = [jnp.asarray(x) for x in (hi, lo, v1, v2, hi2, lo2, w1, w2)]
    (ho, lo_o), (vo1, vo2), nu = pallas_union.sorted_union_columnar_fused_lex2(
        (args[0], args[1]), (args[2], args[3]),
        (args[4], args[5]), (args[6], args[7]), out_size=c,
    )
    for j in range(0, lanes, 17):
        keys, vals, n = su.sorted_union(
            (args[0][:, j], args[1][:, j]), {"a": args[2][:, j], "b": args[3][:, j]},
            (args[4][:, j], args[5][:, j]), {"a": args[6][:, j], "b": args[7][:, j]},
            combine=su.keep_first, out_size=c,
        )
        np.testing.assert_array_equal(np.asarray(keys[0]), np.asarray(ho[:, j]))
        np.testing.assert_array_equal(np.asarray(keys[1]), np.asarray(lo_o[:, j]))
        np.testing.assert_array_equal(np.asarray(vals["a"]), np.asarray(vo1[:, j]))
        np.testing.assert_array_equal(np.asarray(vals["b"]), np.asarray(vo2[:, j]))
        assert int(n) == int(nu[j])
    _log("  lex2 keep-first union: OK")


def _swarm(rng, c, r):
    from benches.bench_oplog_columnar import make_swarm_planes

    return make_swarm_planes(jax.random.key(int(rng.integers(1 << 30))), c, r)


def check_columnar_oplog():
    rng = np.random.default_rng(3)
    a = _swarm(rng, 256, 256)
    b = _swarm(rng, 256, 256)
    m, nu = oc.merge_checked(a, b)
    want, wnu = jax.vmap(oplog.merge_checked)(oc.unstack(a), oc.unstack(b))
    got = oc.unstack(m)
    for f in ("ts", "rid", "seq", "key", "val", "payload", "is_num"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)), err_msg=f
        )
    np.testing.assert_array_equal(np.asarray(nu), np.asarray(wnu))
    conv = oc.converge(a)
    assert (np.asarray(conv.hi) == np.asarray(conv.hi[:, :1])).all()
    _log("  columnar OpLog merge/converge: OK")


def check_sharded():
    rng = np.random.default_rng(5)
    col = _swarm(rng, 256, 128)
    m = mesh_lib.make_mesh(1)
    step = oc.sharded_converge(m, bits=col.bits)  # compiled on TPU
    out, _ = step(col, jnp.ones((128,), bool))
    want = oc.converge(col)
    np.testing.assert_array_equal(np.asarray(out.hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(out.pay), np.asarray(want.pay))
    _log("  sharded_converge (shard_map + Mosaic): OK")


def check_lexn_rseq():
    """The lexN kernel (RSeq's 3·D packed key words + elem/removed planes)
    compiled on the chip vs the generic 4·D-column join."""
    from benches.bench_rseq_columnar import make_swarm_planes
    from crdt_tpu.models import rseq, rseq_columnar as rc

    col = make_swarm_planes(11, 128, 128)
    rows = rc.unstack(col)
    got, nu = rc.merge_checked(
        jax.tree.map(lambda x: x[..., :64], col),
        jax.tree.map(lambda x: x[..., 64:], col),
    )
    a = jax.tree.map(lambda x: x[:64], rows)
    b = jax.tree.map(lambda x: x[64:], rows)
    want, wnu = jax.vmap(rseq.join_checked)(a, b)
    got_rows = rc.unstack(got)
    np.testing.assert_array_equal(
        np.asarray(got_rows.keys), np.asarray(want.keys)
    )
    np.testing.assert_array_equal(
        np.asarray(got_rows.elem), np.asarray(want.elem)
    )
    np.testing.assert_array_equal(
        np.asarray(got_rows.removed), np.asarray(want.removed)
    )
    np.testing.assert_array_equal(np.asarray(nu), np.asarray(wnu))
    _log("  lexN RSeq union (18 key words): OK")


def check_striped_epilogue():
    """The capacity-striped union with the round-5 compaction-only kernel
    epilogue FORCED (the compiled production epilogue above the monolith's
    VMEM envelope), vs the fused monolith interpret oracle — small shapes,
    so the check is cheap while still compiling both the merge-only and
    compact kernels through Mosaic."""
    from benches.bench_rseq_columnar import make_swarm_planes

    col = make_swarm_planes(13, 64, 256, depth=6)
    nk = col.keys.shape[0]
    a = jax.tree.map(lambda x: x[..., :128], col)
    b = jax.tree.map(lambda x: x[..., 128:], col)
    ka = tuple(a.keys[i] for i in range(nk))
    kb = tuple(b.keys[i] for i in range(nk))
    va, vb = (a.elem, a.removed), (b.elem, b.removed)
    interpret = jax.default_backend() != "tpu"
    got = pallas_union.sorted_union_columnar_striped_lexn(
        ka, va, kb, vb, out_size=64, stripe=16,
        interpret=interpret, epilogue="kernel",
    )
    want = pallas_union.sorted_union_columnar_fused_lexn(
        ka, va, kb, vb, out_size=64, interpret=True,
    )
    for g, w in zip(got[0] + got[1] + (got[2],),
                    want[0] + want[1] + (want[2],)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    _log("  striped union, compact-kernel epilogue: OK")


def check_gc_rseq():
    """The GC-aware columnar RSeq join (rseq_engine.gc_merge_checked —
    fused lexN union + floor suppression + 1-key compaction) COMPILED on
    the chip vs the generic tomb_gc join, on a swarm with synthetic
    diverged floors (engine A/B equivalence holds for any input)."""
    from benches.bench_rseq_columnar import make_swarm_planes
    from crdt_tpu.models import rseq, rseq_columnar as rc, rseq_engine, tomb_gc

    c, r, w, seq_bits = 128, 128, 8, 20
    col = make_swarm_planes(13, c, r)
    # rewrite the LAST level's identity word so rids land inside the floor
    # range: element ids are the level-0 identity plane (unique per pool
    # element), so the rewrite is consistent across duplicate copies and
    # cannot perturb the lexicographic row order (earlier planes decide it)
    rng = np.random.default_rng(13)
    rid_of = rng.integers(0, w, 2 * c).astype(np.int64)
    seq_of = rng.integers(0, 400, 2 * c).astype(np.int64)
    k0 = np.asarray(col.keys[0])
    elem_id = np.where(k0 != SENTINEL_PY, np.asarray(col.keys[2]), 0)
    ident = (rid_of[elem_id] << seq_bits) | seq_of[elem_id]
    new_last = np.where(k0 != SENTINEL_PY, ident, SENTINEL_PY).astype(np.int32)
    col = col.replace(keys=col.keys.at[-1].set(jnp.asarray(new_last)))
    half = r // 2
    fa = jnp.asarray(rng.integers(-1, 400, (w, half)), jnp.int32)
    fb = jnp.asarray(rng.integers(-1, 400, (w, half)), jnp.int32)
    a = rseq_engine.ColumnarGc(
        col=jax.tree.map(lambda x: x[..., :half], col), floor=fa)
    b = rseq_engine.ColumnarGc(
        col=jax.tree.map(lambda x: x[..., half:], col), floor=fb)
    got, nu = rseq_engine.gc_merge_checked(a, b)  # compiled Mosaic + XLA

    rows = rc.unstack(col)
    ga = tomb_gc.Gc(inner=jax.tree.map(lambda x: x[:half], rows), floor=fa.T)
    gb = tomb_gc.Gc(inner=jax.tree.map(lambda x: x[half:], rows), floor=fb.T)
    want, wnu = jax.vmap(
        lambda x, y: tomb_gc.join_checked(x, y, rseq.GC_ADAPTER)
    )(ga, gb)
    got_rows = rseq_engine.unstack(got)
    np.testing.assert_array_equal(
        np.asarray(got_rows.inner.keys), np.asarray(want.inner.keys)
    )
    np.testing.assert_array_equal(
        np.asarray(got_rows.inner.elem), np.asarray(want.inner.elem)
    )
    np.testing.assert_array_equal(
        np.asarray(got_rows.inner.removed), np.asarray(want.inner.removed)
    )
    np.testing.assert_array_equal(
        np.asarray(got_rows.floor), np.asarray(want.floor)
    )
    np.testing.assert_array_equal(np.asarray(nu), np.asarray(wnu))
    _log("  GC-aware lexN RSeq join (floor suppression): OK")


def check_sharded_gc():
    """The GC-aware converge under shard_map on a 1-device mesh (compiled
    Mosaic) vs the single-device gc_converge_checked — the production
    tomb_gc barrier path's multichip program (round-5)."""
    from benches.bench_rseq_columnar import make_swarm_planes
    from crdt_tpu.models import rseq_engine

    c, r, w, seq_bits = 64, 16, 8, 20
    col = make_swarm_planes(17, c, r, depth=3)
    rng = np.random.default_rng(17)
    floor = jnp.asarray(rng.integers(-1, 200, (w, r)), jnp.int32)
    cg = rseq_engine.ColumnarGc(col=col, floor=floor)
    alive = jnp.asarray([True] * (r - 1) + [False])
    m = mesh_lib.make_mesh(1)
    step = rseq_engine.sharded_gc_converge(m, depth=3, seq_bits=seq_bits)
    out, _ = step(cg, alive)
    want, _ = rseq_engine.gc_converge_checked(cg, alive)
    np.testing.assert_array_equal(
        np.asarray(out.col.keys), np.asarray(want.col.keys)
    )
    np.testing.assert_array_equal(
        np.asarray(out.col.elem), np.asarray(want.col.elem)
    )
    np.testing.assert_array_equal(
        np.asarray(out.floor), np.asarray(want.floor)
    )
    _log("  sharded GC-aware converge (shard_map + Mosaic): OK")


def run(full=True, log=print):
    """Run the self-test; raises on any kernel/oracle disagreement.

    full=False is the quick subset bench.py gates on — EVERY fused path at
    small shapes: OR-combine C=64, lex2 keep-first, columnar-vs-row-major
    OpLog, shard_map-compiled sharded_converge, the lexN RSeq kernel, the
    GC-aware RSeq join, and the sharded GC-aware converge (round-3 verdict
    item 3: a Mosaic regression in ANY fused path must fail bench.py
    before a headline exists).
    full=True adds only the C=1024 OR-combine shape (the big-compile
    variant; the persistent compile cache makes it one-time per image).
    """
    global _log
    _log = log
    try:
        log(f"devices: {jax.devices()}")
        for c in (64, 1024) if full else (64,):
            check_or_kernel(c)
        check_lex2_kernel()
        check_columnar_oplog()
        check_sharded()
        check_lexn_rseq()
        check_striped_epilogue()
        check_gc_rseq()
        check_sharded_gc()
        log("hw_selftest: ALL OK")
    finally:
        _log = print


def main():
    run(full=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
