"""OpLog store — the flagship model: the reference's replicated key-value
counter store, re-designed as fixed-shape sorted op tensors.

Reference semantics being reproduced (see SURVEY.md §0):

* a replica's durable state is a grow-only op log: timestamp → command
  (/root/reference/main.go:26, main.go:187);
* merge = order-insensitive union of two logs (main.go:49-73);
* the materialized key-value view is rebuilt from the log: per key, the newest
  entry seeds the value and every *numeric* entry accumulates by integer
  addition, i.e. PN-Counter semantics for ints and LWW-Register semantics for
  non-numeric strings (main.go:76-98, main.go:188-207).

TPU-first redesign decisions (each fixes a documented reference quirk,
SURVEY.md §0.1, while preserving observable capability):

* Op identity is the triple ``(ts, rid, seq)`` + the key column — fixing the
  same-millisecond log-key collision (§0.1.2) and making union a true lattice
  join (no local-wins asymmetry needed: identical ops are identical rows).
* Strings are host-interned to int32 ids (crdt_tpu.utils.intern); numeric
  values travel as int32 deltas with an ``is_num`` flag mirroring the
  reference's per-value `strconv.Atoi` probe (main.go:87-96).
* The log is a sorted, sentinel-padded, fixed-capacity tensor; merge is the
  sorted-segment union (crdt_tpu.ops.sorted_union) and the rebuild is two
  scatters — no data-dependent control flow, so the whole pipeline jits and
  vmaps over a replica axis.

The un-fixed reference behaviours (local-op exclusion §0.1.1, tail-drop
§0.1.3, multi-key early-return §0.1.4, …) live in the quirk-togglable oracle
(crdt_tpu.oracle) which is the parity-test ground truth.
"""
from __future__ import annotations

from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from crdt_tpu.ops import joins as _joins
from crdt_tpu.ops import sorted_union as su
from crdt_tpu.utils.constants import SENTINEL


@struct.dataclass
class OpLog:
    """One replica's op log.  Rows sorted by (ts, rid, seq, key); padding rows
    have ts = rid = seq = key = SENTINEL, val = 0, is_num = False."""

    ts: jax.Array       # int32[L] ms offset from host epoch
    rid: jax.Array      # int32[L] writer replica id
    seq: jax.Array      # int32[L] writer-local sequence number
    key: jax.Array      # int32[L] interned key id
    val: jax.Array      # int32[L] numeric delta (0 for non-numeric values)
    payload: jax.Array  # int32[L] interned id of the RAW value string
    is_num: jax.Array   # bool[L]  does the value parse as an integer

    @property
    def capacity(self) -> int:
        return self.ts.shape[-1]


@struct.dataclass
class KVState:
    """Materialized view over an interned key space of size K — the TPU
    encoding of the reference's ``CurrentState`` map (main.go:25).

    Decode rule (see crdt_tpu.api / tests): a key resolves to the raw string
    `payload` when not numeric, OR when numeric with num_count == 1 — the
    reference seeds the newest value *verbatim* (main.go:82-85) and only
    canonicalizes via Itoa once an addition fires (main.go:95-96), so a lone
    "007" stays "007" but "007"+"1" becomes "8"."""

    present: jax.Array    # bool[K]  key has at least one op
    is_num: jax.Array     # bool[K]  resolved value is numeric (counter mode)
    num: jax.Array        # int32[K] counter value (sum of numeric deltas)
    num_count: jax.Array  # int32[K] how many numeric ops contributed
    payload: jax.Array    # int32[K] interned raw string of the newest op


def empty(capacity: int) -> OpLog:
    # one buffer per column: the served merge DONATES its log, and a
    # donation of two leaves sharing a buffer is refused on the chip
    def s():
        return jnp.full((capacity,), SENTINEL, jnp.int32)

    def z():
        return jnp.zeros((capacity,), jnp.int32)

    return OpLog(ts=s(), rid=s(), seq=s(), key=s(), val=z(), payload=z(),
                 is_num=jnp.zeros((capacity,), bool))


def size(log: OpLog) -> jax.Array:
    return jnp.sum(log.ts != SENTINEL).astype(jnp.int32)


def from_ops(capacity: int, ops: Mapping[str, jax.Array]) -> OpLog:
    """Build a log from unsorted op columns (host ingestion path).

    `ops` maps {'ts','rid','seq','key','val','is_num'} to equal-length arrays;
    rows beyond `capacity` must not exist (ingestion batches are host-sized).
    """
    m = ops["ts"].shape[0]
    assert m <= capacity, f"op batch {m} exceeds log capacity {capacity}"
    pad = capacity - m
    s = jnp.full((pad,), SENTINEL, jnp.int32)

    def col(name, fill):
        return jnp.concatenate([jnp.asarray(ops[name]), fill])

    zpad = jnp.zeros((pad,), jnp.int32)
    out = jax.lax.sort(
        [
            col("ts", s), col("rid", s), col("seq", s), col("key", s),
            col("val", zpad), col("payload", zpad),
            col("is_num", jnp.zeros((pad,), bool)),
        ],
        num_keys=4,
        is_stable=True,
    )
    return OpLog(ts=out[0], rid=out[1], seq=out[2], key=out[3],
                 val=out[4], payload=out[5], is_num=out[6])


COLUMNS = ("ts", "rid", "seq", "key", "val", "payload", "is_num")


def from_host_ops(capacity: int, ops: Mapping[str, np.ndarray]) -> OpLog:
    """:func:`from_ops` for host (numpy) op columns, sorted on the host —
    the served ingest path.  ``np.lexsort`` is stable, so the rows land
    in exactly the order of from_ops's stable 4-key device sort, and the
    merge that follows runs no sort on the device at all."""
    m = len(ops["ts"])
    assert m <= capacity, f"op batch {m} exceeds log capacity {capacity}"
    order = np.lexsort((ops["key"], ops["seq"], ops["rid"], ops["ts"]))
    out = {}
    for name in COLUMNS:
        if name == "is_num":
            col = np.zeros(capacity, bool)
        elif name in ("val", "payload"):
            col = np.zeros(capacity, np.int32)
        else:
            col = np.full(capacity, SENTINEL, np.int32)
        col[:m] = ops[name][order]
        out[name] = col
    return OpLog(**out)


MIN_BATCH = 256


def batch_capacity(n: int) -> int:
    """Ingest batch capacity for ``n`` fresh rows: the next power of two,
    at least :data:`MIN_BATCH`, so a stream of batches compiles a handful
    of merge shapes, not one per distinct batch size (a merge's device
    cost is dominated by the log's capacity, not by this padding)."""
    return max(MIN_BATCH, 1 << max(n - 1, 0).bit_length())


@partial(jax.jit, static_argnames="new_capacity")
def grow(log: OpLog, new_capacity: int) -> OpLog:
    """Capacity migration: append tail padding (rows are sorted with
    padding last, so contents and merge results are unchanged).  The host
    layer's overflow recovery (api.node._grow) doubles capacity with this
    before its checked ingest merge."""
    from crdt_tpu.utils.tables import grow_into

    if new_capacity < log.capacity:
        raise ValueError(f"cannot shrink capacity {log.capacity} -> {new_capacity}")
    return grow_into(log, empty(new_capacity))


@jax.jit
def merge(local: OpLog, remote: OpLog) -> OpLog:
    """CRDT join: union of the two logs keyed by (ts, rid, seq, key).

    Replaces the reference's two-pointer walk (main.go:49-73) — without its
    tail-drop quirk (§0.1.3): every remote op is adopted in one merge,
    *provided the union fits the local capacity*.  If it does not, the
    largest (newest) keys are silently dropped — use `merge_checked` where
    overflow must be detected (the host API layer does, and grows the log).
    Identical keys carry identical payloads, so the duplicate combiner is
    keep-first (≡ the reference's local-wins collision rule, main.go:54-65,
    which here is observationally a no-op).
    """
    out, _ = merge_checked(local, remote)
    return out


def _merge_checked(local: OpLog, remote: OpLog):
    # both operands are sorted logs, so the union is a merge of two runs
    # (su.merge_sorted_runs: bit-identical to sorted_union + keep_first,
    # without its two 2C-row sorts or their TPU compile time)
    keys, vals, n_unique = su.merge_sorted_runs(
        (local.ts, local.rid, local.seq, local.key),
        {"val": local.val, "payload": local.payload, "is_num": local.is_num},
        (remote.ts, remote.rid, remote.seq, remote.key),
        {"val": remote.val, "payload": remote.payload, "is_num": remote.is_num},
        out_size=local.capacity,
    )
    return (
        OpLog(
            ts=keys[0], rid=keys[1], seq=keys[2], key=keys[3],
            val=vals["val"], payload=vals["payload"], is_num=vals["is_num"],
        ),
        n_unique,
    )


merge_checked = jax.jit(_merge_checked)
merge_checked.__doc__ = """merge returning (OpLog, n_unique): n_unique >
local.capacity means the true union overflowed and the newest ops were
dropped."""

# The host-ingest variant: donates ``local``'s plane buffers (joins.donating
# — TPU/GPU only; plain jit on CPU) so XLA reuses them for the union output
# instead of writing a fresh 7-plane log every merge.  ONLY for callers
# that drop their reference to ``local`` at the call site — ReplicaNode
# ._ingest rebinds self.log under the node lock (checkpoint saves take the
# same lock, so no thread can read the deleted buffers).  Semantics are
# pinned bit-exact to merge_checked by the lattice-law and parity suites.
merge_checked_donating = _joins.donating(_merge_checked, argnums=(0,))


@partial(jax.jit, static_argnames="n_writers")
def version_vector(log: OpLog, n_writers: int) -> jax.Array:
    """Per-writer received watermark: ``vv[w]`` = max seq of any op authored
    by writer ``w`` in this log, ``-1`` when none.

    Writer seqs are per-writer contiguous from 0 (crdt_tpu.utils.clock.SeqGen)
    and every transfer path (full-state gossip, delta gossip, capacity-
    overflow drop of the globally newest rows) preserves per-writer prefixes,
    so ``seq <= vv[w]`` is exactly "this log already holds that op".  Rows
    with rid outside [0, n_writers) — e.g. a Go peer's rid = -1 ops
    (crdt_tpu.api.node) — have no watermark and are never considered covered.
    """
    valid = (log.ts != SENTINEL) & (log.rid >= 0) & (log.rid < n_writers)
    rid_safe = jnp.where(valid, log.rid, n_writers)
    return (
        jnp.full((n_writers + 1,), -1, jnp.int32)
        .at[rid_safe]
        .max(jnp.where(valid, log.seq, -1))
    )[:n_writers]


def covered_by(log: OpLog, vv: jax.Array) -> jax.Array:
    """bool[L]: which rows a peer holding version vector ``vv`` already has."""
    n_writers = vv.shape[-1]
    valid = log.ts != SENTINEL
    in_range = (log.rid >= 0) & (log.rid < n_writers)
    rid_safe = jnp.clip(log.rid, 0, n_writers - 1)
    return valid & in_range & (log.seq <= vv[rid_safe])


@jax.jit
def delta_since(log: OpLog, vv: jax.Array) -> OpLog:
    """Delta extraction: the sub-log of ops NOT covered by version vector
    ``vv``, canonically re-sorted and padded (same capacity).

    This is the delta-gossip primitive — the reference ships its entire op
    log every round (/root/reference/main.go:159, unbounded payload growth,
    SURVEY.md §6); here a sender keeps only what the receiver is missing.
    The same operation drops already-folded rows after a compaction-frontier
    advance (crdt_tpu.models.compactlog).
    """
    cov = covered_by(log, vv)

    def key_col(c):
        return jnp.where(cov, SENTINEL, c)

    def val_col(c):
        return jnp.where(cov, jnp.zeros_like(c), c)

    out = jax.lax.sort(
        [
            key_col(log.ts), key_col(log.rid), key_col(log.seq),
            key_col(log.key),
            val_col(log.val), val_col(log.payload), val_col(log.is_num),
        ],
        num_keys=4,
        is_stable=True,
    )
    return OpLog(ts=out[0], rid=out[1], seq=out[2], key=out[3],
                 val=out[4], payload=out[5], is_num=out[6])


def append_batch(log: OpLog, ops: Mapping[str, jax.Array], batch_capacity: int | None = None) -> OpLog:
    """Local write path (the reference's AddCommand log append, main.go:187):
    merge a freshly-packed op batch into the log."""
    cap = batch_capacity or log.capacity
    return merge(log, from_ops(cap, ops))


@partial(jax.jit, static_argnames="n_keys")
def rebuild(log: OpLog, n_keys: int) -> KVState:
    """Rebuild the materialized view from the log — the reference's
    newest→oldest fold (main.go:76-98) re-expressed as two scatters:

    * numeric keys: the fold sums every numeric delta (addition commutes, so
      iteration order is irrelevant) → one segment-sum scatter-add;
    * the per-key *newest* op decides the mode: if it is numeric the key is a
      counter valued at the segment sum; otherwise the key is an LWW register
      holding the newest payload (reverse-iteration first-hit, main.go:82-85).
      Because rows are sorted ascending by (ts, rid, seq), "newest" is simply
      the largest row index per key → one scatter-max of row indices.
    """
    valid = log.ts != SENTINEL
    # Out-of-range slot K absorbs padding rows (scatter would otherwise clamp).
    key_safe = jnp.where(valid, log.key, n_keys)

    numeric = valid & log.is_num
    sums = (
        jnp.zeros((n_keys + 1,), jnp.int32)
        .at[key_safe]
        .add(jnp.where(numeric, log.val, 0))
    )[:n_keys]
    num_count = (
        jnp.zeros((n_keys + 1,), jnp.int32)
        .at[key_safe]
        .add(numeric.astype(jnp.int32))
    )[:n_keys]

    idx = jnp.arange(log.capacity, dtype=jnp.int32)
    last = (
        jnp.full((n_keys + 1,), -1, jnp.int32)
        .at[key_safe]
        .max(jnp.where(valid, idx, -1))
    )[:n_keys]

    present = last >= 0
    last_c = jnp.clip(last, 0)
    newest_is_num = log.is_num[last_c] & present
    return KVState(
        present=present,
        is_num=newest_is_num,
        num=jnp.where(newest_is_num, sums, 0),
        num_count=num_count,
        payload=jnp.where(present, log.payload[last_c], 0),
    )


def materialize(kv: KVState, keys, values) -> dict:
    """Decode a KVState back to the reference's {key: string} map using the
    host interners (the inverse of the ingestion encoding).  Implements the
    KVState decode rule: verbatim raw string unless ≥2 numeric ops summed."""
    present = np.asarray(kv.present)
    is_num = np.asarray(kv.is_num)
    num = np.asarray(kv.num)
    num_count = np.asarray(kv.num_count)
    payload = np.asarray(kv.payload)
    out = {}
    for i in range(len(keys)):
        if not present[i]:
            continue
        k = keys.lookup(i)
        if is_num[i] and num_count[i] > 1:
            out[k] = str(int(num[i]))
        else:
            out[k] = values.lookup(int(payload[i]))
    return out
