"""Tombstone GC for tag-identified lattices (OR-Set, RSeq): reclaim the
capacity that removed rows pin, without breaking convergence.

The problem (round-1 verdict item 7): compactlog bounds only the OpLog;
long-lived sets/sequences fill their fixed-capacity tables with tombstoned
tags that the join must keep forever — a naive drop would let a stale
replica re-introduce a dropped tag as live (resurrection).

The fix is the same stable-frontier machinery the OpLog compaction uses
(crdt_tpu.parallel.swarm.stable_frontier), applied to *tag identities*:

* every add-tag carries a writer identity ``(rid, seq)`` with per-writer
  contiguous seqs (SeqWriter/set writers mint 0, 1, 2, …);
* a replica's knowledge watermark is ``received_vv`` = per-writer max seq
  over its table ∨ its floor;
* a **GC barrier** (``gc_round``) first CONVERGES the alive replicas —
  mandatory: collection decisions depend on the *removed flags*, and only
  after convergence do all alive replicas agree on them — then agrees on
  the swarm's stable floor (elementwise min of alive watermarks, chained
  against every existing floor exactly like compactlog's frontier chain
  rule) and drops every row that is ``removed`` AND covered by the floor;
* the floor travels with the state.  The join invariant it maintains:
  **a tag covered by a replica's floor that is absent from its table was
  removed (and collected)**.  ``join`` therefore drops a row that only
  one side holds whenever the *other* side's floor covers it: coverage
  plus absence proves collection.  The holder's own floor is irrelevant —
  a replica can legitimately hold a live floor-covered tag (the floor
  advanced while the tag was live) and still miss a later removal while
  dead; its stale live copy must not survive the rejoin (the gc_soak
  harness caught exactly this).  Matched rows are never suppressed, so a
  straggler's tombstone flag still ORs in (a removal that never gossiped
  out is applied late, not lost).  Absence-implies-collected holds
  because device-level transfers are FULL-STATE unions: a writer's own
  table always carries its whole live-add prefix, so a covered seq can
  disappear only through collection (never through a transfer gap).
  Unchecked capacity overflow stays excluded (use the *_checked joins).
  Delta transport DOES compose with GC at the host layer: the
  floor-carrying delta protocol (crdt_tpu.api.setnode) identifies
  removals as ops, requires a delta receiver's vv to dominate the
  sender's floor, and falls back to a marked full payload (with this
  module's absence-implies-collected suppression) otherwise.

Chain rule and clamping mirror compactlog: floors only advance to
swarm-agreed values, any two live floors are comparable, and ``collect``
clamps the floor advance to the replica's own received watermark.
Capacity-overflow truncation would break per-writer seq contiguity (it
drops by key order, not seq order) — use the ``*_checked`` joins and treat
overflow as an error when GC is enabled, as the host API layers do.

The machinery is generic over an ``adapter`` describing the wrapped
lattice's table layout (key columns, value planes, identity columns);
crdt_tpu.models.orset.GC_ADAPTER and crdt_tpu.models.rseq adapters
instantiate it.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from crdt_tpu.ops import sorted_union as su
from crdt_tpu.utils.constants import SENTINEL


@struct.dataclass
class Gc:
    """A tag-identified lattice plus its per-writer GC floor."""

    inner: Any          # the wrapped state (ORSet, RSeq, …)
    floor: jax.Array    # int32[W]  per-writer collected watermark (-1 = none)

    @property
    def n_writers(self) -> int:
        return self.floor.shape[-1]


def wrap(inner: Any, n_writers: int) -> Gc:
    """Wrap a plain lattice state (nothing collected yet: floor = -1)."""
    return Gc(inner=inner, floor=jnp.full((n_writers,), -1, jnp.int32))


def _covered(rid, seq, valid, floor):
    """bool[C]: rows whose identity the floor covers (rid out of range —
    e.g. a foreign peer's ops — is never covered, like oplog.covered_by)."""
    w = floor.shape[-1]
    in_range = (rid >= 0) & (rid < w)
    rid_safe = jnp.clip(rid, 0, w - 1)
    return valid & in_range & (seq <= floor[rid_safe])


@partial(jax.jit, static_argnames="adapter")
def received_vv(g: Gc, adapter) -> jax.Array:
    """Per-writer knowledge watermark: table max-seq ∨ floor."""
    rid, seq = adapter.rid_seq(g.inner)
    valid = adapter.valid(g.inner)
    w = g.n_writers
    rid_safe = jnp.where(valid & (rid >= 0) & (rid < w), rid, w)
    table_vv = (
        jnp.full((w + 1,), -1, jnp.int32)
        .at[rid_safe]
        .max(jnp.where(valid, seq, -1))
    )[:w]
    return jnp.maximum(g.floor, table_vv)


def next_seq(g: Gc, adapter, rid: int) -> int:
    """First safe seq for writer ``rid`` to mint on this replica: above
    everything observed OR collected.  Re-minting a collected (rid, seq)
    identity would be silently suppressed at the next join — writers that
    restart into a GC'd state must resume their counters from here (see
    rseq.SeqWriter's seq_start contract)."""
    return int(received_vv(g, adapter)[rid]) + 1


@partial(jax.jit, static_argnames="adapter")
def join_checked(a: Gc, b: Gc, adapter):
    """GC-aware CRDT join (see module docstring for the suppression rule).
    Returns (Gc, n_unique): n_unique counts post-suppression unique rows;
    > capacity means truncation broke the state (treat as an error when GC
    is active — seq contiguity is a GC invariant)."""
    # explicit if/raise, not assert (asserts vanish under python -O, and
    # sorted_union's own n_keys assert would zip-truncate a mixed-depth
    # join into silent corruption); shapes are static, so these checks run
    # once per trace
    ka, kb = adapter.key_cols(a.inner), adapter.key_cols(b.inner)
    if len(ka) != len(kb) or any(x.shape != y.shape for x, y in zip(ka, kb)):
        raise ValueError(
            f"GC join requires identical key layouts: "
            f"{[x.shape for x in ka]} vs {[y.shape for y in kb]} "
            "(mixed-depth RSeq states must be widened to a common depth "
            "before joining)"
        )
    if adapter.capacity_of(a.inner) != adapter.capacity_of(b.inner):
        raise ValueError(
            f"GC join requires equal capacities ({adapter.capacity_of(a.inner)}"
            f" vs {adapter.capacity_of(b.inner)}) — the output is sliced to "
            "one capacity, so unequal tables would make the join asymmetric; "
            "grow() the smaller state first"
        )
    if a.floor.shape != b.floor.shape:
        raise ValueError(
            f"GC join requires equal writer counts: floor shapes "
            f"{a.floor.shape} vs {b.floor.shape}"
        )
    # src marker rides the value planes: 1 = only a, 2 = only b, 3 = both
    va = {"v": adapter.vals(a.inner), "src": jnp.ones_like(adapter.valid(a.inner), jnp.int32)}
    vb = {"v": adapter.vals(b.inner), "src": jnp.full_like(adapter.valid(b.inner), 2, jnp.int32)}

    def combine(x, y):
        return {"v": adapter.combine(x["v"], y["v"]), "src": x["src"] | y["src"]}

    # lossless union first (out_size = n_a + n_b); suppression and the
    # capacity slice happen after, so a suppressed row never evicts a real one
    keys, vals, _ = su.sorted_union(
        adapter.key_cols(a.inner), va, adapter.key_cols(b.inner), vb,
        combine=combine, out_size=None,
    )
    full = adapter.from_union(keys, vals["v"])
    rid, seq = adapter.rid_seq(full)
    valid = adapter.valid(full)
    only_a = vals["src"] == 1
    only_b = vals["src"] == 2
    drop = (only_a & _covered(rid, seq, valid, b.floor)) | (
        only_b & _covered(rid, seq, valid, a.floor)
    )
    keys2 = [jnp.where(drop, SENTINEL, k) for k in keys]
    flat, treedef = jax.tree.flatten(adapter.vals_zero_like(full, drop))
    out = jax.lax.sort(
        list(keys2) + flat, num_keys=len(keys2), is_stable=True
    )
    keys3 = out[: len(keys2)]
    vals3 = jax.tree.unflatten(treedef, out[len(keys2):])
    n_unique = jnp.sum(keys3[0] != SENTINEL).astype(jnp.int32)
    cap = adapter.capacity_of(a.inner)
    inner = adapter.from_union(
        [k[:cap] for k in keys3], jax.tree.map(lambda x: x[:cap], vals3)
    )
    return Gc(inner=inner, floor=jnp.maximum(a.floor, b.floor)), n_unique


def join(a: Gc, b: Gc, adapter) -> Gc:
    """Convenience join that REFUSES capacity overflow (GcOverflow) instead
    of silently truncating — truncation drops by key order, not seq order,
    which breaks the per-writer contiguity the floor-coverage proof rests
    on (silent permanent data loss).  The host-side n_unique check forces a
    device sync; throughput paths (vmapped barriers) use ``join_checked``
    and batch the check like gc_round does.

    GC joins are PINNED to the sort path (recorded on the union_path
    tally): the src-marker suppression rule needs the full row union with
    per-row provenance, which the bitmap/bucket layouts don't carry."""
    from crdt_tpu.ops import union_engine

    union_engine.record_union_path("sort")
    out, n_unique = join_checked(a, b, adapter)
    cap = adapter.capacity_of(a.inner)
    if int(n_unique) > cap:
        union_engine.record_truncation()
        raise GcOverflow(
            f"GC join needs {int(n_unique)} rows but capacity is {cap}"
        )
    return out


@partial(jax.jit, static_argnames="adapter")
def collect(g: Gc, new_floor: jax.Array, adapter) -> Gc:
    """Advance the floor and drop every row that is removed AND covered.

    ``new_floor`` must come from a swarm-agreed barrier over CONVERGED
    alive replicas (gc_round) — convergence is what makes the removed
    flags agree, so every alive replica drops the same rows.  As a hard
    safety net the advance is clamped to this replica's own received
    watermark (a floor beyond ops never received would make join's
    suppression rule drop rows that were never collected)."""
    floor = jnp.maximum(g.floor, jnp.minimum(new_floor, received_vv(g, adapter)))
    rid, seq = adapter.rid_seq(g.inner)
    valid = adapter.valid(g.inner)
    drop = _covered(rid, seq, valid, floor) & adapter.removed_of(g.inner)
    keys = [jnp.where(drop, SENTINEL, k) for k in adapter.key_cols(g.inner)]
    flat, treedef = jax.tree.flatten(adapter.vals_zero_like(g.inner, drop))
    out = jax.lax.sort(list(keys) + flat, num_keys=len(keys), is_stable=True)
    inner = adapter.from_union(
        out[: len(keys)], jax.tree.unflatten(treedef, out[len(keys):])
    )
    return Gc(inner=inner, floor=floor)


class GcOverflow(RuntimeError):
    """A GC-barrier join truncated the union at table capacity.  Truncation
    drops by key order, not seq order, so it breaks the per-writer seq
    contiguity that received_vv/stable-floor coverage proofs rest on —
    advancing a floor over truncated rows would turn the drop into
    permanent, silent data loss.  The barrier refuses instead."""


def gc_round(sw, adapter, neutral_inner, engine: str = "auto"):
    """One swarm-wide GC barrier over a Swarm of Gc states: converge the
    alive replicas (flag agreement), then agree on the stable floor
    (chain-ruled against every existing floor, dead replicas' included)
    and collect it everywhere alive.  Dead replicas keep their state and
    floor; one GC-aware join catches them up on revival.

    The convergence phase rides the adapter's columnar fused-kernel
    engine by DEFAULT when it declares one (``adapter.columnar_converge``
    — rseq.GC_ADAPTER does; the hook warns EngineFallback and returns
    None when the layout is ineligible, and the generic vmapped
    reduction serves).  ``engine="generic"`` pins the generic path (the
    A/B reference).

    The convergence runs through CHECKED joins and raises GcOverflow if
    any pairwise union truncated — the floor must never advance over
    silently-dropped rows (see GcOverflow)."""
    from crdt_tpu.ops import joins as joins_mod
    from crdt_tpu.parallel import swarm as swarm_mod
    from crdt_tpu.obs.trace import region

    neutral = wrap(neutral_inner, sw.state.floor.shape[-1])
    jbc = jax.vmap(lambda x, y: join_checked(x, y, adapter))
    cap = adapter.capacity_of(neutral_inner)

    with region("crdt.tomb_gc.barrier"):
        converged = None
        hook = getattr(adapter, "columnar_converge", None)
        if engine != "generic" and hook is not None:
            res = hook(sw)
            if res is not None:
                converged, max_nu = res
                if max_nu > cap:
                    raise GcOverflow(
                        f"GC barrier union needs {max_nu} rows but "
                        f"capacity is {cap}"
                    )
        if converged is None:
            # generic fallback: the same log-depth tree reduction
            # joins.tree_reduce_join runs, unrolled here so each level's
            # n_unique is observable host-side
            state = joins_mod.pad_to_pow2(
                swarm_mod.mask_dead_with_neutral(sw.state, sw.alive, neutral),
                neutral,
            )
            max_nu = 0
            p = jax.tree.leaves(state)[0].shape[0]
            while p > 1:
                p //= 2
                lo = jax.tree.map(lambda x: x[:p], state)
                hi = jax.tree.map(lambda x: x[p : 2 * p], state)
                state, nu = jbc(lo, hi)
                max_nu = max(max_nu, int(nu.max()))
            if max_nu > cap:
                raise GcOverflow(
                    f"GC barrier union needs {max_nu} rows but capacity "
                    f"is {cap}"
                )
            top = jax.tree.map(lambda x: x[0], state)
            converged = sw.replace(
                state=swarm_mod.broadcast_where_alive(sw.state, sw.alive, top)
            )
        return swarm_mod.compaction_round(
            converged,
            received_vv=lambda st: received_vv(st, adapter),
            compact=lambda st, f: collect(st, f, adapter),
            frontier_of=lambda st: st.floor,
        )
