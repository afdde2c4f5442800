"""Columnar swarm layout for RSeq — the lexN Pallas fast path.

A swarm of RSeq states (crdt_tpu.models.rseq) in the row-major [R, C, 4D]
vmap layout joins through the generic XLA sorted_union: a full O(n log²n)
sort over 4·D key columns per merge — the heaviest key rows in the
framework riding the slowest engine (round-2 verdict item 3).  This module
gives the same state the columnar layout the OpLog fast path uses (replica
axis on TPU lanes, table rows on sublanes; see crdt_tpu.ops.pallas_union
for why that layout wins), with the 4·D path-key columns bit-packed into
3 int32 words per level, so swarm-scale RSeq convergence rides the fused
lexN bitonic-merge kernel (sorted_union_columnar_fused_lexn) instead.

Per-level pack (order-preserving; no field straddles a word):

* word 0: ``p_hi`` — the position's top 30 bits (< 2^30, so a real row's
  HEAD plane can never equal SENTINEL: the kernel's hole detection and
  padding order stay sound for free);
* word 1: ``p_lo`` — the position's low 30 bits (< 2^30);
* word 2: ``rid << seq_bits | seq`` — the writer identity, budgets fitted
  host-side at stack time exactly like oplog_columnar.stack (an
  out-of-budget field would bleed across its bit boundary and silently
  corrupt the sort order — stack() validates and raises).

Lexicographic order over the 3·D packed words equals lexicographic order
over the original 4·D columns: each original column occupies a distinct
word (or a distinct bit range of one) in original column order.

Value planes: ``elem`` (payload id, identical on both copies of a
duplicate key — op identity) and ``removed`` (monotone 0/1 tombstone).
The kernel's duplicate rule is OR-combine-then-keep-first
(pallas_union._make_lexn_union_kernel): ``elem`` passes through unchanged
(x | x == x) and ``removed`` gets true join semantics — a removal held by
only one side survives whichever copy the network keeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct
from jax import shard_map

from crdt_tpu.models import rseq
from crdt_tpu.ops import pallas_union
from crdt_tpu.utils.constants import SENTINEL, SENTINEL_PY

HALF_BITS = rseq.HALF_BITS  # 30: both position words stay under 2^30


@struct.dataclass
class ColumnarRSeq:
    """A swarm of R sequence tables as (·, C, R) planes: lane j = replica
    j's table, per-lane sorted ascending by the packed key words; padding
    rows have every key word = SENTINEL, elem = removed = 0."""

    keys: jax.Array     # int32[3*D, C, R]  packed path-key words
    elem: jax.Array     # int32[C, R]       payload id
    removed: jax.Array  # int32[C, R]       tombstone (0/1; monotone)
    seq_bits: int = struct.field(pytree_node=False, default=20)

    @property
    def depth(self) -> int:
        return self.keys.shape[0] // 3

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]

    @property
    def lanes(self) -> int:
        return self.keys.shape[2]


def fit_seq_bits(n_writers: int, max_seq: int) -> int:
    """Seq-field width for the identity word: rid gets what it needs, seq
    the rest; raises when the pair cannot share 31 bits."""
    rid_bits = max(1, (max(n_writers, 1) - 1).bit_length())
    seq_bits = 31 - rid_bits
    if max_seq >= 1 << seq_bits:
        raise ValueError(
            f"(rid < {n_writers}, seq <= {max_seq}) needs more than the "
            "31-bit identity-word budget"
        )
    return seq_bits


def plan(states: rseq.RSeq, seq_bits: int | None = None):
    """Auto-selection for RSeq swarms, mirroring oplog_engine.plan: stage
    into the columnar lexN engine whenever the identity budgets allow,
    fall back LOUDLY (an ``oplog_engine.EngineFallback`` warning naming
    the violated budget) to the row-major generic path otherwise.

    Returns ``(ColumnarRSeq, None)`` on the fast path or ``(None, reason)``
    on fallback — callers keep the batched row-major state and drive it
    through ``jax.vmap(rseq.join)`` / swarm.converge as before."""
    import warnings

    from crdt_tpu.models.oplog_engine import EngineFallback

    try:
        cap = states.keys.shape[-2]
        if cap & (cap - 1):
            raise ValueError(
                f"capacity {cap} is not a power of two (bitonic network)"
            )
        return stack(states, seq_bits=seq_bits), None
    except ValueError as e:
        warnings.warn(
            f"RSeq swarm fell back to the generic engine: {e}",
            EngineFallback,
            stacklevel=2,
        )
        return None, str(e)


def stack(states: rseq.RSeq, seq_bits: int | None = None) -> ColumnarRSeq:
    """Stage a batched [R, C, 4D] RSeq (or a single [C, 4D] state) into
    columnar planes.  Host-side: validates every identity field against
    the pack budget; with ``seq_bits=None`` the split is fitted from the
    observed ranges (rid gets what the data needs, seq the rest).  Rows
    are already sorted in path-key order, which the pack preserves."""
    import numpy as np

    keys = np.asarray(states.keys)
    if keys.ndim == 2:
        keys = keys[None]
    elem = np.atleast_2d(np.asarray(states.elem))
    removed = np.atleast_2d(np.asarray(states.removed))
    r, c, w = keys.shape
    if w % 4:
        raise ValueError(f"key width {w} is not 4*depth")
    d = w // 4
    valid = keys[:, :, 0] != SENTINEL_PY
    v3 = valid[:, :, None]

    rid_cols = keys[:, :, 2::4]
    seq_cols = keys[:, :, 3::4]
    rid_max = int(np.where(v3, rid_cols, 0).max(initial=0))
    rid_min = int(np.where(v3, rid_cols, 0).min(initial=0))
    seq_max = int(np.where(v3, seq_cols, 0).max(initial=0))
    seq_min = int(np.where(v3, seq_cols, 0).min(initial=0))
    if rid_min < 0 or seq_min < 0:
        raise ValueError(
            f"negative identity field (rid>={rid_min}, seq>={seq_min}) "
            "cannot bit-pack order-preservingly"
        )
    if seq_bits is None:
        seq_bits = fit_seq_bits(rid_max + 1, seq_max)
    rid_bits = 31 - seq_bits
    if rid_max >= 1 << rid_bits or seq_max >= 1 << seq_bits:
        raise ValueError(
            f"identity range (rid<={rid_max}, seq<={seq_max}) exceeds the "
            f"(rid:{rid_bits}, seq:{seq_bits}) split"
        )
    for name, col in (("p_hi", keys[:, :, 0::4]), ("p_lo", keys[:, :, 1::4])):
        lo = int(np.where(v3, col, 0).min(initial=0))
        hi = int(np.where(v3, col, 0).max(initial=0))
        if lo < 0 or hi >= 1 << HALF_BITS:
            raise ValueError(
                f"{name} range [{lo}, {hi}] outside the 30-bit position word"
            )

    planes = np.empty((3 * d, c, r), np.int32)
    vt = valid.T  # (C, R)
    kt = keys.transpose(2, 1, 0)  # (4D, C, R)
    for lvl in range(d):
        planes[3 * lvl + 0] = np.where(vt, kt[4 * lvl + 0], SENTINEL_PY)
        planes[3 * lvl + 1] = np.where(vt, kt[4 * lvl + 1], SENTINEL_PY)
        ident = (kt[4 * lvl + 2] << seq_bits) | kt[4 * lvl + 3]
        planes[3 * lvl + 2] = np.where(vt, ident, SENTINEL_PY)
    return ColumnarRSeq(
        keys=jnp.asarray(planes),
        elem=jnp.asarray(np.where(vt, elem.T, 0).astype(np.int32)),
        removed=jnp.asarray(np.where(vt, removed.T, 0).astype(np.int32)),
        seq_bits=int(seq_bits),
    )


@jax.jit
def unstack(col: ColumnarRSeq) -> rseq.RSeq:
    """Back to the batched [R, C, 4D] row-major RSeq (exact inverse of
    stack)."""
    d = col.depth
    valid = col.keys[0] != SENTINEL  # (C, R)
    s = jnp.full_like(col.keys[0], SENTINEL)
    cols = []
    for lvl in range(d):
        ident = col.keys[3 * lvl + 2]
        cols += [
            jnp.where(valid, col.keys[3 * lvl + 0], s),
            jnp.where(valid, col.keys[3 * lvl + 1], s),
            jnp.where(valid, ident >> col.seq_bits, s),
            jnp.where(valid, ident & ((1 << col.seq_bits) - 1), s),
        ]
    keys = jnp.stack(cols, axis=0).transpose(2, 1, 0)  # (R, C, 4D)
    return rseq.RSeq(
        keys=keys,
        elem=jnp.where(valid, col.elem, 0).T,
        removed=(jnp.where(valid, col.removed, 0) != 0).T,
    )


def _pad_lanes(col: ColumnarRSeq, lanes: int) -> ColumnarRSeq:
    pad = lanes - col.lanes
    if pad == 0:
        return col
    return ColumnarRSeq(
        keys=jnp.pad(col.keys, ((0, 0), (0, 0), (0, pad)),
                     constant_values=int(SENTINEL)),
        elem=jnp.pad(col.elem, ((0, 0), (0, pad))),
        removed=jnp.pad(col.removed, ((0, 0), (0, pad))),
        seq_bits=col.seq_bits,
    )


def _slice_lanes(col: ColumnarRSeq, lo: int, hi: int) -> ColumnarRSeq:
    return jax.tree.map(lambda x: x[..., lo:hi], col)


def merge_checked(a: ColumnarRSeq, b: ColumnarRSeq, interpret: bool = False):
    """Lane-wise CRDT join through the fused lexN kernel: lane j of the
    result is the capacity-bounded union of lane j of ``a`` and ``b`` with
    tombstone-OR on duplicates.  Returns (ColumnarRSeq, n_unique[R]);
    n_unique[j] > capacity means lane j's true union overflowed and the
    largest keys were dropped (same contract as rseq.join_checked)."""
    # if/raise, not assert: silent-element-loss failure modes
    if a.keys.shape[0] != b.keys.shape[0]:
        raise ValueError(
            f"depths differ ({a.depth} vs {b.depth}): widen to a common "
            "depth before joining (rseq.widen)"
        )
    if a.seq_bits != b.seq_bits:
        raise ValueError(
            f"pack layouts differ (seq_bits {a.seq_bits} vs {b.seq_bits})"
        )
    if a.capacity != b.capacity:
        raise ValueError(f"capacities differ ({a.capacity} vs {b.capacity})")
    if a.lanes != b.lanes:
        raise ValueError(f"lane counts differ ({a.lanes} vs {b.lanes})")
    lanes = a.lanes
    padded = -lanes % pallas_union.LANES
    if padded:
        a = _pad_lanes(a, lanes + padded)
        b = _pad_lanes(b, lanes + padded)
    nk = a.keys.shape[0]
    # auto: one fused pallas_call inside the VMEM envelope, the
    # capacity-striped block network beyond it (full-depth C>256)
    keys, (elem, removed), nu = pallas_union.sorted_union_columnar_lexn_auto(
        tuple(a.keys[i] for i in range(nk)), (a.elem, a.removed),
        tuple(b.keys[i] for i in range(nk)), (b.elem, b.removed),
        out_size=a.capacity, interpret=interpret,
    )
    out = ColumnarRSeq(
        keys=jnp.stack(keys, axis=0), elem=elem, removed=removed,
        seq_bits=a.seq_bits,
    )
    if padded:
        out = _slice_lanes(out, 0, lanes)
        nu = nu[:lanes]
    return out, nu


def merge(a: ColumnarRSeq, b: ColumnarRSeq, interpret: bool = False) -> ColumnarRSeq:
    out, _ = merge_checked(a, b, interpret=interpret)
    return out


def mask_dead(col: ColumnarRSeq, alive: jax.Array) -> ColumnarRSeq:
    """Dead replicas' lanes become empty tables (the join identity)."""
    a = alive[None, :]
    return ColumnarRSeq(
        keys=jnp.where(a[None], col.keys, SENTINEL),
        elem=jnp.where(a, col.elem, 0),
        removed=jnp.where(a, col.removed, 0),
        seq_bits=col.seq_bits,
    )


def lub_lane(
    col: ColumnarRSeq, alive: jax.Array | None = None, interpret: bool = False
):
    """Log-depth lane-halving tree reduction to a SINGLE-lane least upper
    bound of the alive lanes.  Returns (one-lane ColumnarRSeq, max nu)."""
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


def _broadcast_top(
    col: ColumnarRSeq, top: ColumnarRSeq, alive: jax.Array | None
) -> ColumnarRSeq:
    """Broadcast a one-lane LUB over the alive lanes of ``col`` (dead
    lanes keep their stale tables) — shared by the single-device and
    sharded converge paths so their dead-lane semantics cannot diverge."""
    out = jax.tree.map(
        lambda t, x: jnp.broadcast_to(t[..., :1], x.shape), top, col
    )
    if alive is None:
        return out
    return ColumnarRSeq(
        keys=jnp.where(alive[None, None, :], out.keys, col.keys),
        elem=jnp.where(alive[None, :], out.elem, col.elem),
        removed=jnp.where(alive[None, :], out.removed, col.removed),
        seq_bits=col.seq_bits,
    )


def converge_checked(
    col: ColumnarRSeq, alive: jax.Array | None = None, interpret: bool = False
):
    """Drive every alive lane to the least upper bound of alive lanes'
    tables — swarm.converge for the sequence CRDT on the fused kernel.
    Returns (ColumnarRSeq, max_n_unique); max_n_unique > capacity means
    some pairwise union truncated."""
    from crdt_tpu.obs.trace import region

    with region("crdt.rseq_columnar.converge"):
        work, max_nu = lub_lane(col, alive, interpret=interpret)
        return _broadcast_top(col, work, alive), max_nu


def converge(
    col: ColumnarRSeq, alive: jax.Array | None = None, interpret: bool = False
) -> ColumnarRSeq:
    out, _ = converge_checked(col, alive, interpret=interpret)
    return out


def sharded_converge(
    mesh,
    depth: int = rseq.DEPTH,
    seq_bits: int = 20,
    axis: str = "replica",
    interpret: bool | None = None,
):
    """Multi-chip columnar RSeq convergence: the lane (replica) axis
    sharded over a device mesh, the fused lexN kernel doing every merge —
    the sequence-CRDT sibling of oplog_columnar.sharded_converge, same
    three-phase program:

      1. each device tree-reduces its local lane shard to a one-lane LUB
         (lub_lane — all fused-kernel merges, no cross-device traffic);
      2. one ``all_gather`` ships the P single-lane LUBs over ICI/DCN —
         the ONLY collective, moving (3·D + 2) planes × C rows × P lanes;
      3. each device reduces the gathered lanes to the global LUB and
         broadcasts it over its local alive lanes.

    Build once per mesh; the returned jitted ``step(col, alive)`` returns
    ``(col, max_n_unique)``.  ``interpret`` defaults to True off TPU."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def local_step(keys, elem, removed, alive):
        col = ColumnarRSeq(keys=keys, elem=elem, removed=removed,
                           seq_bits=seq_bits)
        local_lub, nu_local = lub_lane(col, alive, interpret=interpret)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True),
            local_lub,
        )
        top, nu_global = lub_lane(gathered, interpret=interpret)
        out = _broadcast_top(col, top, alive)
        # per-device nu values differ: pmax keeps the replicated out_spec
        # truthful (same reasoning as oplog_columnar.sharded_converge)
        max_nu = jax.lax.pmax(jnp.maximum(nu_local, nu_global), axis)
        return out.keys, out.elem, out.removed, max_nu

    shmapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(None, None, axis), P(None, axis), P(None, axis),
                  P(axis)),
        out_specs=(P(None, None, axis), P(None, axis), P(None, axis), P()),
        check_vma=False,  # pallas out_shapes carry no varying-axes note
    )

    @jax.jit
    def step(col: ColumnarRSeq, alive: jax.Array):
        if col.seq_bits != seq_bits or col.depth != depth:
            raise ValueError(
                f"state (depth={col.depth}, seq_bits={col.seq_bits}) does "
                f"not match this step (depth={depth}, seq_bits={seq_bits})"
            )
        keys, elem, removed, max_nu = shmapped(
            col.keys, col.elem, col.removed, alive
        )
        return (
            ColumnarRSeq(keys=keys, elem=elem, removed=removed,
                         seq_bits=seq_bits),
            max_nu,
        )

    return step


def gossip_round(
    col: ColumnarRSeq,
    peers: jax.Array,
    alive: jax.Array | None = None,
    interpret: bool = False,
) -> ColumnarRSeq:
    """One pull round in the columnar layout: lane j fetches lane peers[j]
    and joins it, gated on both endpoints being alive."""
    peer = jax.tree.map(lambda x: x[..., peers], col)
    merged = merge(col, peer, interpret=interpret)
    if alive is None:
        return merged
    ok = alive & alive[peers]
    return ColumnarRSeq(
        keys=jnp.where(ok[None, None, :], merged.keys, col.keys),
        elem=jnp.where(ok[None, :], merged.elem, col.elem),
        removed=jnp.where(ok[None, :], merged.removed, col.removed),
        seq_bits=col.seq_bits,
    )
