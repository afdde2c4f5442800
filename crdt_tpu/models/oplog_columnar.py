"""Columnar swarm layout for the flagship OpLog — the Pallas fast path.

A swarm of OpLogs (crdt_tpu.models.oplog) in the row-major [R, C] vmap
layout merges through the generic XLA sorted_union: a full O(n log^2 n)
sort of the concatenation per merge.  This module gives the SAME state the
columnar layout the OR-Set fast path uses (replica axis on TPU lanes,
log rows on sublanes; see crdt_tpu.ops.pallas_union for why that layout
wins) so swarm-scale OpLog convergence rides the fused bitonic-merge
union kernel instead — the round-1 verdict's "best kernel on the shelf"
fix.

Key encoding: the op identity is the 4-tuple (ts, rid, seq, key)
(crdt_tpu.models.oplog.OpLog — the fixed version of the reference's
bare-timestamp log key, /root/reference/main.go:187, SURVEY.md §0.1.2).
The kernel compares a lexicographic two-word key
(crdt_tpu.ops.pallas_union.sorted_union_columnar_fused_lex2):

* ``hi``  = ts (int32 ms offset, non-negative, < SENTINEL);
* ``lo``  = rid | seq | key bit-packed, order-preserving, sign bit clear —
  budgets are explicit per layout and checked host-side at stack time
  (a field overflowing its budget would bleed across bit boundaries and
  silently corrupt the sort order).

Value planes: ``val`` (numeric delta) and ``pay`` = payload | is_num<<31
(the payload intern id is non-negative, so the sign bit carries the
is_num flag for free — one plane fewer through VMEM and HBM).

Duplicates resolve keep-first inside the kernel: identical (ts, rid, seq,
key) is the same op carrying identical values, the op-identity invariant
the row-major path relies on too (crdt_tpu.ops.sorted_union.keep_first).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from flax import struct
from jax import shard_map

from crdt_tpu.models import oplog
from crdt_tpu.ops import pallas_union
from crdt_tpu.utils.constants import SENTINEL

# Default lo-word split: 256 writers x 64K ops/writer x 128 interned keys.
# (The reference demo's key space is the 62-char alphabet,
# /root/reference/main.go:274.)  Override per layout via stack(..., bits=).
DEFAULT_BITS = (8, 16, 7)


@struct.dataclass
class ColumnarOpLog:
    """A swarm of R op logs as (C, R) planes: lane j = replica j's log,
    per-lane sorted ascending by (hi, lo); padding rows have
    hi = lo = SENTINEL, val = pay = 0."""

    hi: jax.Array   # int32[C, R]  ts
    lo: jax.Array   # int32[C, R]  rid | seq | key (order-preserving pack)
    val: jax.Array  # int32[C, R]  numeric delta
    pay: jax.Array  # int32[C, R]  payload intern id | is_num << 31
    bits: tuple = struct.field(pytree_node=False, default=DEFAULT_BITS)

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]

    @property
    def lanes(self) -> int:
        return self.hi.shape[1]


def check_bits(bits) -> None:
    rid_bits, seq_bits, key_bits = bits
    if min(rid_bits, seq_bits, key_bits) < 1:
        raise ValueError(
            f"pack split {bits} has a non-positive field width — the fields "
            "would overlap and silently corrupt the packed sort order"
        )
    if rid_bits + seq_bits + key_bits > 31:
        raise ValueError(
            f"pack split {bits} exceeds 31 bits (sign bit must stay clear)"
        )


def fit_bits(n_writers: int, n_keys: int) -> tuple:
    """A lo-word split for a known layout: rid/key get exactly what they
    need, seq takes the rest (the axis that actually grows over time)."""
    rid_bits = max(1, (n_writers - 1).bit_length())
    key_bits = max(1, (n_keys - 1).bit_length())
    bits = (rid_bits, 31 - rid_bits - key_bits, key_bits)
    check_bits(bits)
    return bits


def pack_id(rid, seq, key, bits):
    rid_bits, seq_bits, key_bits = bits
    del rid_bits
    return ((rid << (seq_bits + key_bits)) | (seq << key_bits) | key).astype(
        jnp.int32
    )


def unpack_id(lo, bits):
    rid_bits, seq_bits, key_bits = bits
    key = lo & ((1 << key_bits) - 1)
    seq = (lo >> key_bits) & ((1 << seq_bits) - 1)
    rid = (lo >> (seq_bits + key_bits)) & ((1 << rid_bits) - 1)
    return rid, seq, key


def empty(capacity: int, lanes: int, bits=DEFAULT_BITS) -> ColumnarOpLog:
    s = jnp.full((capacity, lanes), SENTINEL, jnp.int32)
    z = jnp.zeros((capacity, lanes), jnp.int32)
    return ColumnarOpLog(hi=s, lo=s, val=z, pay=z, bits=tuple(bits))


def stack(logs: oplog.OpLog, bits=DEFAULT_BITS) -> ColumnarOpLog:
    """Stage a batched [R, C] OpLog (or a single [C] log) into the columnar
    planes.  Host-side: validates every field against the pack budget —
    out-of-budget ids would silently corrupt the kernel's sort order.
    Rows must already be in the oplog sort order (ts, rid, seq, key), which
    every OpLog constructor guarantees; the packed (hi, lo) order is
    identical because the pack is order-preserving."""
    import numpy as np

    check_bits(bits)
    rid_bits, seq_bits, key_bits = bits
    ts, rid, seq, key = map(
        jnp.atleast_2d, (logs.ts, logs.rid, logs.seq, logs.key)
    )
    val = jnp.atleast_2d(logs.val)
    payload = jnp.atleast_2d(logs.payload)
    is_num = jnp.atleast_2d(logs.is_num)
    valid = ts != SENTINEL

    def _field_max(x):
        return int(np.asarray(jnp.where(valid, x, 0)).max(initial=0))

    def _field_min(x):
        return int(np.asarray(jnp.where(valid, x, 0)).min(initial=0))

    for name, x, limit in (
        ("rid", rid, 1 << rid_bits),
        ("seq", seq, 1 << seq_bits),
        ("key", key, 1 << key_bits),
    ):
        lo_v, hi_v = _field_min(x), _field_max(x)
        if lo_v < 0 or hi_v >= limit:
            raise ValueError(
                f"{name} range [{lo_v}, {hi_v}] exceeds the packed budget "
                f"[0, {limit}) for bits={bits}; use a wider split or the "
                "generic row-major path (crdt_tpu.models.oplog.merge)"
            )
    if _field_min(ts) < 0:
        raise ValueError("negative ts cannot ride the columnar layout")
    # (ts == SENTINEL cannot be caught here: the valid mask IS that
    # encoding — the guard lives at mint/ingest time, api/node.py)
    if _field_min(payload) < 0:
        raise ValueError("negative payload id cannot carry the is_num bit")

    hi = jnp.where(valid, ts, SENTINEL)
    lo = jnp.where(valid, pack_id(rid, seq, key, bits), SENTINEL)
    pay = jnp.where(
        valid, payload | (is_num.astype(jnp.int32) << 31), 0
    )
    return ColumnarOpLog(
        hi=hi.T, lo=lo.T, val=jnp.where(valid, val, 0).T, pay=pay.T,
        bits=tuple(bits),
    )


@jax.jit
def unstack(col: ColumnarOpLog) -> oplog.OpLog:
    """Back to the batched [R, C] row-major OpLog (exact inverse of stack)."""
    hi, lo = col.hi.T, col.lo.T
    valid = hi != SENTINEL
    rid, seq, key = unpack_id(jnp.where(valid, lo, 0), col.bits)
    pay = jnp.where(valid, col.pay.T, 0)
    s = jnp.full_like(hi, SENTINEL)
    return oplog.OpLog(
        ts=hi,
        rid=jnp.where(valid, rid, s),
        seq=jnp.where(valid, seq, s),
        key=jnp.where(valid, key, s),
        val=jnp.where(valid, col.val.T, 0),
        payload=pay & 0x7FFFFFFF,
        is_num=pay < 0,
    )


@partial(jax.jit, static_argnames="new_capacity")
def grow(col: ColumnarOpLog, new_capacity: int) -> ColumnarOpLog:
    """Capacity migration in the columnar layout: append tail padding
    ROWS (per-lane sorted order keeps padding last).  new_capacity must
    stay a power of two (the kernel's bitonic network requires it)."""
    from crdt_tpu.utils.tables import grow_into

    if new_capacity < col.capacity:
        raise ValueError(
            f"cannot shrink capacity {col.capacity} -> {new_capacity}"
        )
    if new_capacity & (new_capacity - 1):
        raise ValueError(f"capacity {new_capacity} must be a power of two")
    return grow_into(col, empty(new_capacity, col.lanes, col.bits))


def _pad_lanes(col: ColumnarOpLog, lanes: int) -> ColumnarOpLog:
    pad = lanes - col.lanes
    if pad == 0:
        return col
    return ColumnarOpLog(
        hi=jnp.pad(col.hi, ((0, 0), (0, pad)), constant_values=int(SENTINEL)),
        lo=jnp.pad(col.lo, ((0, 0), (0, pad)), constant_values=int(SENTINEL)),
        val=jnp.pad(col.val, ((0, 0), (0, pad))),
        pay=jnp.pad(col.pay, ((0, 0), (0, pad))),
        bits=col.bits,
    )


def _slice_lanes(col: ColumnarOpLog, lo: int, hi: int) -> ColumnarOpLog:
    return jax.tree.map(lambda x: x[:, lo:hi], col)


def merge_checked(a: ColumnarOpLog, b: ColumnarOpLog, interpret: bool = False):
    """Lane-wise CRDT join through the fused kernel: lane j of the result is
    the capacity-bounded union of lane j of ``a`` and ``b``.  Returns
    (ColumnarOpLog, n_unique[R]); n_unique[j] > capacity means lane j's true
    union overflowed and the newest ops were dropped (same contract as
    oplog.merge_checked).  Lane counts off the kernel's 128-lane tile are
    padded here and sliced back off."""
    # if/raise, not assert: these vanish under python -O and the failure
    # mode they guard is silent op loss
    if a.bits != b.bits:
        raise ValueError(f"pack layouts differ: {a.bits} vs {b.bits}")
    if a.capacity != b.capacity:
        raise ValueError(
            f"capacities differ ({a.capacity} vs {b.capacity}): the block "
            "specs built from a's shape would silently read only b's head rows"
        )
    if a.lanes != b.lanes:
        raise ValueError(
            f"lane counts differ ({a.lanes} vs {b.lanes}): the grid built "
            "from a's shape would clamp b's out-of-bounds blocks and merge "
            "the wrong replicas' logs"
        )
    lanes = a.lanes
    padded = -lanes % pallas_union.LANES
    if padded:
        a = _pad_lanes(a, lanes + padded)
        b = _pad_lanes(b, lanes + padded)
    (hi, lo), (val, pay), nu = pallas_union.sorted_union_columnar_fused_lex2(
        (a.hi, a.lo), (a.val, a.pay), (b.hi, b.lo), (b.val, b.pay),
        out_size=a.capacity, interpret=interpret,
    )
    out = ColumnarOpLog(hi=hi, lo=lo, val=val, pay=pay, bits=a.bits)
    if padded:
        out = _slice_lanes(out, 0, lanes)
        nu = nu[:lanes]
    return out, nu


def merge(a: ColumnarOpLog, b: ColumnarOpLog, interpret: bool = False) -> ColumnarOpLog:
    out, _ = merge_checked(a, b, interpret=interpret)
    return out


def mask_dead(col: ColumnarOpLog, alive: jax.Array) -> ColumnarOpLog:
    """Dead replicas' lanes become empty logs (the join identity), exactly
    like swarm.mask_dead_with_neutral — an unreachable peer contributes
    nothing (/root/reference/main.go:235-239's 502-skip)."""
    a = alive[None, :]
    return ColumnarOpLog(
        hi=jnp.where(a, col.hi, SENTINEL),
        lo=jnp.where(a, col.lo, SENTINEL),
        val=jnp.where(a, col.val, 0),
        pay=jnp.where(a, col.pay, 0),
        bits=col.bits,
    )


def lub_lane(
    col: ColumnarOpLog, alive: jax.Array | None = None, interpret: bool = False
):
    """Log-depth lane-halving tree reduction to a SINGLE-lane least upper
    bound of the alive lanes (dead lanes contribute the join identity).
    Returns (one-lane ColumnarOpLog, max_n_unique across the reduction).
    The building block of converge/sharded_converge."""
    work = col if alive is None else mask_dead(col, alive)
    p = 1
    while p < col.lanes:
        p *= 2
    work = _pad_lanes(work, p)
    max_nu = jnp.zeros((), jnp.int32)
    while p > 1:
        p //= 2
        work, nu = merge_checked(
            _slice_lanes(work, 0, p), _slice_lanes(work, p, 2 * p),
            interpret=interpret,
        )
        max_nu = jnp.maximum(max_nu, nu.max())
    return work, max_nu


def converge_checked(
    col: ColumnarOpLog, alive: jax.Array | None = None, interpret: bool = False
):
    """Drive every alive lane to the least upper bound of alive lanes' logs
    — swarm.converge for the flagship model, routed through the Pallas
    kernel.  A log-depth lane-halving tree reduction computes the LUB, then
    it broadcasts back over the alive lanes; dead lanes keep their stale
    state.  Returns (ColumnarOpLog, max_n_unique): max_n_unique > capacity
    means some pairwise union overflowed (newest ops dropped) — the same
    silent-truncation contract as the generic path, made checkable."""
    from crdt_tpu.obs.trace import region

    lanes = col.lanes
    with region("crdt.oplog_columnar.converge"):
        work, max_nu = lub_lane(col, alive, interpret=interpret)
        top = jax.tree.map(
            lambda x: jnp.broadcast_to(x[:, :1], (col.capacity, lanes)), work
        )
        if alive is not None:
            a = alive[None, :]
            top = jax.tree.map(lambda t, x: jnp.where(a, t, x), top, col)
        return top, max_nu


def converge(
    col: ColumnarOpLog, alive: jax.Array | None = None, interpret: bool = False
) -> ColumnarOpLog:
    out, _ = converge_checked(col, alive, interpret=interpret)
    return out


def gossip_round(
    col: ColumnarOpLog,
    peers: jax.Array,
    alive: jax.Array | None = None,
    interpret: bool = False,
) -> ColumnarOpLog:
    """One pull round in the columnar layout: lane j fetches lane peers[j]
    and joins it (swarm.gossip_round semantics: the join is gated on both
    endpoints being alive)."""
    peer = jax.tree.map(lambda x: x[:, peers], col)
    merged = merge(col, peer, interpret=interpret)
    if alive is None:
        return merged
    ok = (alive & alive[peers])[None, :]
    return jax.tree.map(lambda m, x: jnp.where(ok, m, x), merged, col)


@partial(jax.jit, static_argnames="n_keys")
def rebuild(col: ColumnarOpLog, n_keys: int) -> oplog.KVState:
    """Per-lane materialized view (batched KVState over the lane axis):
    unpack + the standard two-scatter rebuild (oplog.rebuild)."""
    return jax.vmap(lambda lg: oplog.rebuild(lg, n_keys))(unstack(col))


def sharded_converge(
    mesh,
    bits=DEFAULT_BITS,
    axis: str = "replica",
    interpret: bool | None = None,
):
    """Multi-chip columnar convergence: the lane (replica) axis sharded
    over a device mesh, the fused kernel doing every merge.

    Build once per mesh; the returned jitted ``step(col, alive)`` runs one
    global anti-entropy fixpoint and returns ``(col, max_n_unique)``:

      1. each device tree-reduces its local lane shard to a one-lane LUB
         (lub_lane — all Pallas merges, no cross-device traffic);
      2. one ``all_gather`` ships the P single-lane LUBs over ICI/DCN —
         the ONLY collective, moving 4 planes × C rows × P lanes;
      3. each device reduces the gathered lanes to the global LUB and
         broadcasts it over its local alive lanes.

    This is the columnar sibling of parallel.mesh.sharded_converge: same
    barrier semantics, but local reduction work rides the fused kernel
    instead of the generic XLA sort.  ``interpret`` defaults to True off
    TPU (CPU meshes — tests, the driver's virtual-device dryrun) and
    False on TPU."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def local_step(hi, lo, val, pay, alive):
        col = ColumnarOpLog(hi=hi, lo=lo, val=val, pay=pay, bits=tuple(bits))
        local_lub, nu_local = lub_lane(col, alive, interpret=interpret)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, axis=1, tiled=True),
            local_lub,
        )
        top, nu_global = lub_lane(gathered, interpret=interpret)
        out = jax.tree.map(
            lambda t, x: jnp.where(
                alive[None, :],
                jnp.broadcast_to(t[:, :1], x.shape), x,
            ),
            top, col,
        )
        # per-device nu_local values differ: pmax them so the P() out_spec
        # (replicated scalar) is truthful
        max_nu = jax.lax.pmax(jnp.maximum(nu_local, nu_global), axis)
        return out.hi, out.lo, out.val, out.pay, max_nu

    shmapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(None, axis),) * 4 + (P(axis),),
        out_specs=(P(None, axis),) * 4 + (P(),),
        # pallas_call's out_shapes carry no varying-mesh-axes annotation,
        # which the vma checker rejects; the manual pmax above keeps the
        # replicated scalar out_spec truthful without it
        check_vma=False,
    )

    @jax.jit
    def step(col: ColumnarOpLog, alive: jax.Array):
        if col.bits != tuple(bits):
            raise ValueError(
                f"state packed with bits={col.bits} but this step was built "
                f"for bits={tuple(bits)}: the output would be relabeled and "
                "unpack to garbage"
            )
        hi, lo, val, pay, max_nu = shmapped(
            col.hi, col.lo, col.val, col.pay, alive
        )
        return (
            ColumnarOpLog(hi=hi, lo=lo, val=val, pay=pay, bits=tuple(bits)),
            max_nu,
        )

    return step
