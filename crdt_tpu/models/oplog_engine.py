"""Engine auto-selection for OpLog swarms: the fused columnar kernel by
default, the generic XLA path as the loud exception.

Round-2 gap being closed: the ×5.5 columnar fast path
(crdt_tpu.models.oplog_columnar, the lex2 Pallas kernel) existed but was
opt-in — nothing selected it, so every swarm.converge-level consumer rode
the generic O(n log²n) sorted_union.  This module is the selector:
``plan()`` inspects a batched row-major swarm ONCE (host-side), picks the
columnar engine whenever the layout allows, and falls back LOUDLY
(``EngineFallback`` warning + recorded reason) to row-major otherwise.

Columnar eligibility — all checked host-side at plan time, never silently:

* capacity is a power of two (the kernel's bitonic network requires it);
* every (rid, seq, key) fits an order-preserving 31-bit pack
  (``oplog_columnar.fit_bits`` sizes the split from the observed field
  ranges; ``oplog_columnar.stack`` re-validates every field against it);
* ts and payload are non-negative (their sign bits carry the SENTINEL
  padding and the is_num flag respectively).

The returned :class:`OpLogSwarm` keeps the state RESIDENT in its engine's
layout — repeated converge/gossip calls re-stack nothing; ``rows()`` is
the only transposing accessor.

The reference system this replaces converges by per-pair JSON merges at
~0.67 rounds/s/replica (/root/reference/main.go:226-261); either engine
here collapses the whole fixpoint into one jitted call — the engine choice
only decides which kernel does the row work.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from crdt_tpu.models import oplog, oplog_columnar as oc
from crdt_tpu.utils.constants import SENTINEL_PY


class EngineFallback(UserWarning):
    """The swarm layout cannot ride the columnar fused kernel; the generic
    row-major engine was selected instead.  The message says exactly which
    budget failed — fix the layout (grow to a power-of-two capacity, widen
    the pack split, renumber foreign rids) to get the fast path back."""


def _field_range(x, valid):
    x = np.asarray(x)
    v = np.asarray(valid)
    if not v.any():
        return 0, 0
    vals = x[v]
    return int(vals.min()), int(vals.max())


def columnar_plan(state: oplog.OpLog):
    """Host-side eligibility check for the columnar engine over a batched
    [R, C] swarm.  Returns (bits, None) when eligible, (None, reason) when
    the generic path must serve."""
    cap = state.capacity
    if cap & (cap - 1):
        return None, f"capacity {cap} is not a power of two (bitonic network)"
    valid = np.asarray(state.ts) != SENTINEL_PY
    ts_min, _ = _field_range(state.ts, valid)
    if ts_min < 0:
        return None, f"negative ts {ts_min} cannot carry the SENTINEL sign bit"
    # NOTE: a row AT ts == SENTINEL cannot be gated here — the valid mask
    # above is that same encoding, so such a row is indistinguishable
    # from padding in ANY engine.  The guard lives at mint/ingest time
    # (api/node.py add_command + receive reject ts >= INT32_MAX).
    pay_min, _ = _field_range(state.payload, valid)
    if pay_min < 0:
        return None, f"negative payload id {pay_min} cannot carry the is_num bit"
    rid_min, rid_max = _field_range(state.rid, valid)
    seq_min, seq_max = _field_range(state.seq, valid)
    key_min, key_max = _field_range(state.key, valid)
    if min(rid_min, seq_min, key_min) < 0:
        return None, (
            f"negative identity field (rid>={rid_min}, seq>={seq_min}, "
            f"key>={key_min}) cannot bit-pack order-preservingly"
        )
    rid_bits = max(1, rid_max.bit_length())
    key_bits = max(1, key_max.bit_length())
    seq_bits = max(1, seq_max.bit_length())
    if rid_bits + seq_bits + key_bits > 31:
        return None, (
            f"identity ranges (rid<{rid_max + 1}, seq<{seq_max + 1}, "
            f"key<{key_max + 1}) need {rid_bits + seq_bits + key_bits} bits "
            "> the 31-bit pack budget"
        )
    # give seq the whole slack: it is the axis that grows as history does,
    # so a resident swarm keeps its engine for as long as possible
    return (rid_bits, 31 - rid_bits - key_bits, key_bits), None


class OpLogSwarm:
    """A swarm of R op logs resident in the fastest engine its layout
    allows.  Build with :func:`plan`; ``engine`` is ``"columnar"`` or
    ``"generic"``, ``fallback_reason`` records why when generic."""

    def __init__(self, *, col=None, rows=None, alive, interpret,
                 fallback_reason=None):
        assert (col is None) != (rows is None)
        self._col = col
        self._rows = rows
        self.alive = alive
        self.interpret = interpret
        self.fallback_reason = fallback_reason

    # ---- introspection ----

    @property
    def engine(self) -> str:
        return "generic" if self._col is None else "columnar"

    @property
    def n_replicas(self) -> int:
        return self.alive.shape[0]

    @property
    def capacity(self) -> int:
        return self._rows.capacity if self._col is None else self._col.capacity

    @property
    def columnar(self) -> Optional[oc.ColumnarOpLog]:
        """The resident columnar planes (None on the generic engine) — for
        callers that drive the sharded path (oc.sharded_converge) directly."""
        return self._col

    def rows(self) -> oplog.OpLog:
        """The swarm as a batched [R, C] row-major OpLog (transposes on the
        columnar engine — an accessor, not the hot path)."""
        return self._rows if self._col is None else oc.unstack(self._col)

    def _wrap(self, col=None, rows=None, alive=None):
        return OpLogSwarm(
            col=col, rows=rows,
            alive=self.alive if alive is None else alive,
            interpret=self.interpret,
            fallback_reason=self.fallback_reason,
        )

    # ---- swarm ops (one call = the reference's many-round gossip) ----

    def converge_checked(self):
        """Drive every alive replica to the alive-set LUB; returns
        (OpLogSwarm, max_n_unique).  max_n_unique > capacity means some
        pairwise union truncated (newest ops dropped) — same contract on
        both engines, so A/B comparisons are exact."""
        if self._col is not None:
            col, nu = _col_converge_checked(
                self._col, self.alive, interpret=self.interpret
            )
            return self._wrap(col=col), nu
        state, nu = _generic_converge_checked(self._rows, self.alive)
        return self._wrap(rows=state), nu

    def converge(self) -> "OpLogSwarm":
        out, _ = self.converge_checked()
        return out

    def gossip_round(self, peers) -> "OpLogSwarm":
        """One pull round: replica j joins peers[j]'s log, gated on both
        endpoints alive (the reference's 502-skip, main.go:235-239)."""
        if self._col is not None:
            return self._wrap(col=_col_gossip_round(
                self._col, peers, self.alive, interpret=self.interpret
            ))
        return self._wrap(rows=_generic_gossip_round(
            self._rows, peers, self.alive))

    def set_alive(self, rid, alive_status) -> "OpLogSwarm":
        return self._wrap(
            col=self._col, rows=self._rows,
            alive=self.alive.at[rid].set(alive_status),
        )

    def rebuild(self, n_keys: int) -> oplog.KVState:
        """Per-replica materialized views (batched over the replica axis)."""
        if self._col is not None:
            return oc.rebuild(self._col, n_keys)
        return jax.vmap(lambda l: oplog.rebuild(l, n_keys))(self._rows)


def plan(
    state: oplog.OpLog,
    alive: jax.Array | None = None,
    bits: tuple | None = None,
    force_generic: bool = False,
    interpret: bool | None = None,
) -> OpLogSwarm:
    """Build the swarm engine for a batched [R, C] row-major OpLog.

    Columnar (fused Pallas kernel) is the DEFAULT: it is selected whenever
    :func:`columnar_plan` finds a valid layout (or the caller pins ``bits``).
    The generic row-major engine is the exception, and falling back to it
    warns ``EngineFallback`` with the precise reason — silent degradation
    is how fast paths rot.

    ``interpret`` routes the kernel through Pallas interpret mode; default
    False on TPU, True elsewhere (CPU tests / the driver's virtual mesh).
    """
    r = state.ts.shape[0]
    if alive is None:
        alive = jnp.ones((r,), bool)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if force_generic:
        return OpLogSwarm(rows=state, alive=alive, interpret=interpret,
                          fallback_reason="forced by caller")
    if bits is None:
        bits, reason = columnar_plan(state)
        if bits is None:
            warnings.warn(
                f"OpLog swarm fell back to the generic engine: {reason}",
                EngineFallback,
                stacklevel=2,
            )
            return OpLogSwarm(rows=state, alive=alive, interpret=interpret,
                              fallback_reason=reason)
    return OpLogSwarm(col=oc.stack(state, bits=bits), alive=alive,
                      interpret=interpret)


# each swarm step is ONE compiled program: the tree reductions are
# Python loops over halving shapes, which run eagerly would compile every
# slice and merge of every level separately (minutes on a chip at 10K
# lanes)
_col_converge_checked = jax.jit(oc.converge_checked,
                                static_argnames="interpret")
_col_gossip_round = jax.jit(oc.gossip_round, static_argnames="interpret")


@jax.jit
def _generic_gossip_round(state: oplog.OpLog, peers, alive):
    from crdt_tpu.parallel import swarm as swarm_mod

    s = swarm_mod.Swarm(state=state, alive=alive)
    return swarm_mod.gossip_round(s, peers, jax.vmap(oplog.merge)).state


@jax.jit
def _generic_converge_checked(state: oplog.OpLog, alive: jax.Array):
    """The row-major fallback of converge_checked: alive-masked log-depth
    tree reduction through the generic sorted_union, overflow tracked level
    by level (mirrors oc.lub_lane so both engines share one contract)."""
    from crdt_tpu.ops import joins
    from crdt_tpu.parallel import swarm as swarm_mod

    neutral = oplog.empty(state.capacity)
    work = joins.pad_to_pow2(
        swarm_mod.mask_dead_with_neutral(state, alive, neutral), neutral
    )
    jbc = jax.vmap(oplog.merge_checked)
    max_nu = jnp.zeros((), jnp.int32)
    p = work.ts.shape[0]
    while p > 1:
        p //= 2
        lo = jax.tree.map(lambda x: x[:p], work)
        hi = jax.tree.map(lambda x: x[p : 2 * p], work)
        work, nu = jbc(lo, hi)
        max_nu = jnp.maximum(max_nu, nu.max())
    top = jax.tree.map(lambda x: x[0], work)
    return swarm_mod.broadcast_where_alive(state, alive, top), max_nu
