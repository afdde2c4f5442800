"""ReplicaNode: the host-side replica — the TPU-native answer to the
reference's `Server` struct (/root/reference/main.go:23-33).

Mirrors the five capabilities of the reference's HTTP surface as plain
methods (the HTTP shim in crdt_tpu.api.http_shim wraps them 1:1):

  add_command  <- POST /data   (main.go:173-215)
  get_state    <- GET  /data   (main.go:129-139)
  gossip_payload / receive <- GET /gossip + the pull loop (main.go:154-171,
                               226-261)
  ping         <- GET  /ping   (main.go:115-127)
  set_alive    <- GET  /condition (main.go:141-152; routing bug §0.1.7 fixed)

Distributed-honesty note: gossip payloads carry STRINGS (like the Go JSON
wire format), and each node interns into its own table on receipt — two
nodes never need to share an interner, so the same code path works across
process/host boundaries.  The in-process swarm engine (crdt_tpu.parallel)
is the shared-interner fast path.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from crdt_tpu.models import compactlog, oplog
from crdt_tpu.obs import devtime, health
from crdt_tpu.ops import union_engine
from crdt_tpu.obs.events import EventLog
from crdt_tpu.obs.provenance import FlightRecorder
from crdt_tpu.obs.trace import current_trace, span
from crdt_tpu.utils.clock import HostClock, SeqGen
from crdt_tpu.utils.intern import Interner, encode_value
from crdt_tpu.utils.metrics import Metrics

# Wire key for an op: "ts:rid:seq" (the fixed, collision-free op identity —
# reference quirk §0.1.2 fixed).  Timestamps travel as ABSOLUTE Unix
# milliseconds — nodes in different processes have different int32 epochs,
# so the wire carries the epoch-free value and each receiver rebases onto
# its own epoch.  Plain integer keys (a Go peer's UnixMilli log keys,
# main.go:187) are accepted with rid=-1, seq=0.
INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1

# Reserved payload sections for compaction-aware gossip (delta-CRDT mode,
# crdt_tpu.models.compactlog).  NOT part of the Go-compatible wire surface: a
# reference peer would choke on these keys (its malformed-key path kills its
# gossip loop, quirk §0.1.8) — so compaction stays off (the reference's own
# behavior: it never prunes, main.go:75) unless the deployment opts in via
# ClusterConfig.compact_every / explicit compact() calls.
FRONTIER_KEY = "__frontier__"
SUMMARY_KEY = "__summary__"


def _summary_entry(e: Dict[str, Any]) -> Dict[str, Any]:
    """Normalize one wire-shaped summary entry (the single schema definition
    — used by payload adoption and by the device-summary decoder)."""
    return {
        "num": int(e["num"]),
        "num_count": int(e["num_count"]),
        "ts": int(e["ts"]),
        "rid": int(e["rid"]),
        "seq": int(e["seq"]),
        "payload": str(e["payload"]),
        "is_num": bool(e["is_num"]),
    }


def _wire_key(ts_abs: int, rid: int, seq: int) -> str:
    return f"{ts_abs}:{rid}:{seq}"


def _parse_wire_key(k: str) -> Tuple[int, int, int]:
    if ":" in k:
        ts, rid, seq = k.split(":")
        return int(ts), int(rid), int(seq)
    return int(k), -1, 0  # Go-format key: millisecond timestamp only


def stable_frontier_host(vvs, frontiers) -> Dict[int, int]:
    """The host-side stable-frontier computation shared by every barrier
    scheduler (LocalCluster.compact, net.network_compact): the per-writer
    min over the member version vectors ``vvs``, valid only if it dominates
    every existing fold in ``frontiers`` (the chain rule — a non-dominating
    barrier would mint an incomparable frontier generation).  Returns {}
    when no barrier is possible this round."""
    rids = set().union(*vvs)
    frontier = {
        r: s
        for r in rids
        if (s := min(vv.get(r, -1) for vv in vvs)) >= 0
    }
    for f in frontiers:
        for r, s in f.items():
            if frontier.get(r, -1) < s:
                return {}
    return frontier


def pull_round(node: "ReplicaNode", fetch_payload, metrics, delta: bool,
               prefix: str = "gossip", peer: Optional[str] = None,
               trace: Optional[str] = None, quarantine: bool = False) -> bool:
    """One anti-entropy pull into ``node`` — the shared round body of every
    gossip driver (in-process LocalCluster, cross-process NetworkAgent): ask
    the peer for a (delta) payload, merge it, and keep the skip/noop/fresh
    counters consistent across transports.

    ``fetch_payload(since)`` returns the peer's payload dict, or None for an
    unreachable/dead peer (the reference's 502-skip, main.go:235-239).

    ``peer``/``trace`` feed the observability layer: the round's outcome is
    emitted to ``node.events`` under the gossip round's trace ID, and the
    delta-payload op count is recorded as the lag-behind-``peer`` gauge
    (crdt_tpu.obs.health) — in delta mode that count IS how many ops this
    node lacked.

    ``quarantine=True`` (the network drivers) turns a MALFORMED payload —
    bad wire keys, out-of-window timestamps, truncated summary sections,
    wrong-shaped commands — into a skipped round with a
    ``payload_quarantine`` event and a ``{prefix}_quarantined`` count,
    instead of an exception that kills the caller's gossip loop.  The
    in-process LocalCluster keeps the loud-raise default: there a
    malformed payload is a local bug, not a hostile network.
    """
    lab = str(node.rid)
    if not node.alive:
        metrics.inc(f"{prefix}_skipped")
        node.events.emit("pull_skip", trace=trace, peer=peer, reason="down")
        return False
    with span(f"crdt.pull_round.{prefix}", trace) as tid:
        since = node.version_vector() if delta else None
        payload = fetch_payload(since)
        if payload is None:
            metrics.inc(f"{prefix}_skipped")
            node.events.emit("pull_skip", trace=tid, peer=peer,
                             reason="peer_unreachable")
            return False
        n_ops = sum(
            1 for k in payload if k not in (FRONTIER_KEY, SUMMARY_KEY)
        )
        if delta:
            health.observe_pull_lag(metrics.registry, lab, peer or "?", n_ops)
        if not payload:  # delta mode: peer had nothing we lack — no merge
            metrics.inc(f"{prefix}_noop")
            node.events.emit("pull_noop", trace=tid, peer=peer)
            return False
        metrics.inc(f"{prefix}_payload_ops", n_ops)
        try:
            fresh = node.receive(payload)
        except (ValueError, KeyError, TypeError) as e:
            if not quarantine:
                raise
            metrics.inc(f"{prefix}_quarantined")
            node.events.emit("payload_quarantine", trace=tid, peer=peer,
                             surface=prefix,
                             error=f"{type(e).__name__}: {e}"[:200])
            return False
        if not fresh:  # payload was all re-deliveries (e.g. foreign ops)
            metrics.inc(f"{prefix}_noop")
            node.events.emit("pull_noop", trace=tid, peer=peer, ops=n_ops)
            return False
        metrics.inc(f"{prefix}_rounds")
        health.mark_merge(metrics.registry, lab)
        node.events.emit("pull_merge", trace=tid, peer=peer, ops=n_ops,
                         fresh=fresh)
        return True


def fused_pull_round(node: "ReplicaNode", fetched, metrics, delta: bool,
                     prefix: str = "gossip",
                     trace: Optional[str] = None,
                     quarantine: bool = False) -> bool:
    """The k-way sibling of :func:`pull_round` — the pipelined merge
    runtime's round body.  ``fetched`` is a list of ``(peer_label,
    payload_or_None)`` pairs the driver already collected (concurrently in
    NetworkAgent, in-process in LocalCluster), all requested against the
    SAME pre-round version vector; every non-empty payload is merged in ONE
    device dispatch via :meth:`ReplicaNode.receive_many`, so a P-peer round
    costs 1 merge dispatch instead of P (pinned by the merge_dispatches
    counter, tests/test_pipeline.py).

    Per-peer skip/noop accounting matches the sequential path exactly: an
    unreachable peer counts one ``{prefix}_skipped``, an empty delta one
    ``{prefix}_noop``, and the lag gauges are observed per peer — only the
    merge itself is fused.
    """
    lab = str(node.rid)
    if not node.alive:
        metrics.inc(f"{prefix}_skipped")
        node.events.emit("pull_skip", trace=trace, reason="down")
        return False
    with span(f"crdt.fused_pull_round.{prefix}", trace) as tid:
        payloads, labels, total_ops = [], [], 0
        for peer, payload in fetched:
            if payload is None:
                metrics.inc(f"{prefix}_skipped")
                node.events.emit("pull_skip", trace=tid, peer=peer,
                                 reason="peer_unreachable")
                continue
            n_ops = sum(
                1 for k in payload if k not in (FRONTIER_KEY, SUMMARY_KEY)
            )
            if delta:
                health.observe_pull_lag(metrics.registry, lab,
                                        peer or "?", n_ops)
            if not payload:  # delta mode: this peer had nothing we lack
                metrics.inc(f"{prefix}_noop")
                node.events.emit("pull_noop", trace=tid, peer=peer)
                continue
            if quarantine:
                # pre-validate so ONE malformed payload quarantines alone
                # instead of poisoning the whole fused dispatch
                bad = node.validate_payload(payload)
                if bad is not None:
                    metrics.inc(f"{prefix}_quarantined")
                    node.events.emit("payload_quarantine", trace=tid,
                                     peer=peer, surface=prefix,
                                     error=bad[:200])
                    continue
            payloads.append(payload)
            labels.append(peer)
            total_ops += n_ops
        if not payloads:
            return False
        health.observe_fused_pull(metrics.registry, lab, len(payloads))
        metrics.inc(f"{prefix}_payload_ops", total_ops)
        try:
            fresh = node.receive_many(payloads)
        except (ValueError, KeyError, TypeError) as e:
            if not quarantine:
                raise
            metrics.inc(f"{prefix}_quarantined")
            node.events.emit("payload_quarantine", trace=tid, peers=labels,
                             surface=prefix,
                             error=f"{type(e).__name__}: {e}"[:200])
            return False
        if not fresh:  # every payload was re-deliveries
            metrics.inc(f"{prefix}_noop")
            node.events.emit("pull_noop", trace=tid, peers=labels,
                             ops=total_ops)
            return False
        metrics.inc(f"{prefix}_rounds")
        health.mark_merge(metrics.registry, lab)
        node.events.emit("pull_merge_fused", trace=tid, peers=labels,
                         ops=total_ops, fresh=fresh)
        return True


class PendingMerge:
    """One plane's decoded + accepted (but NOT yet merged) ingest batch.

    Produced by :meth:`ReplicaNode.merge_begin` /
    :meth:`ReplicaNode.add_commands_begin` with the node lock HELD — it
    stays held until :meth:`commit` / :meth:`commit_inline` /
    :meth:`abort` — so the device-mesh plane
    (crdt_tpu.parallel.meshplane) can fold MANY planes' batches in one
    fused dispatch while each plane's host bookkeeping (command map,
    delta indexes, vv) lands exactly where the inline path puts it.
    Commit rebinds the merged log and finishes the metrics/recorder
    accounting the inline path does after its own dispatch.
    """

    __slots__ = ("node", "ops", "fresh", "adopted", "rows", "births",
                 "vv_before", "recording", "done", "dig", "dig_sum")

    def __init__(self, node: "ReplicaNode"):
        self.node = node
        self.ops: Optional[Dict[str, np.ndarray]] = None
        self.fresh = 0
        self.adopted = 0
        # decoded wire rows (recorder tenant attribution on commit)
        self.rows: List[Tuple[int, int, int, Dict[str, str]]] = []
        # locally-minted (seq, abs_ts) birth stamps (add_commands_begin)
        self.births: List[Tuple[int, int]] = []
        self.vv_before: Optional[Dict[int, int]] = None
        self.recording = False
        self.done = False
        # audit-digest carry (crdt_tpu.obs.audit): per-row digest lanes
        # of the packed batch (fresh, 4 uint32) + their host-side lane
        # sum — the mesh plane folds the same rows on-device inside its
        # fused dispatch and commit() verifies the two sums bit-equal
        self.dig: Optional[np.ndarray] = None
        self.dig_sum: Optional[np.ndarray] = None

    def rows_held(self) -> int:
        """Live log rows of the plane (caller of the fused step sizes the
        uniform lane capacity from this; the lock is held so it's stable)."""
        n = self.node._log_rows
        if n is None:
            n = int(oplog.size(self.node.log))
            self.node._log_rows = n
        return n

    def commit(self, merged_log, n_unique: int, digest=None) -> int:
        """Finish the deferred merge with the FUSED step's output lane:
        rebind the log, finish accounting, release the node lock.
        ``n_unique`` must already be a host int (the mesh plane syncs the
        whole lane-count vector in one transfer).  ``digest`` (optional)
        is the device-folded lane sum of this lane's audit-digest rows,
        synced in the same transfer — bit-compared against the host-side
        sum (continuous mesh-vs-host digest parity; a mismatch emits
        ``audit_mesh_mismatch`` rather than failing the merge, since the
        merged log itself is already checked by the sorted union)."""
        node = self.node
        try:
            if self.fresh:
                assert n_unique <= merged_log.ts.shape[-1], (
                    f"fused union {n_unique} rows overflowed lane capacity "
                    f"{merged_log.ts.shape[-1]}")
                if digest is not None and self.dig_sum is not None:
                    dev = np.asarray(digest, np.uint32)
                    if not np.array_equal(dev, self.dig_sum):
                        from crdt_tpu.ops import digest as digkernel

                        node.metrics.inc("audit_mesh_mismatch")
                        node.events.emit(
                            "audit_mesh_mismatch",
                            host=digkernel.digest_hex(self.dig_sum),
                            device=digkernel.digest_hex(dev))
                node.log = merged_log
                node._log_rows = int(n_unique)
                node.metrics.inc("ops_ingested", self.fresh)
                node._count_lane_fold()
            self._finish_recording()
        finally:
            self.done = True
            node._lock.release()
        return self.fresh + self.adopted

    def commit_inline(self) -> int:
        """Fallback: run THIS lane's merge as the inline host dispatch
        (one jitted merge, exactly `_merge_batch`) and finish accounting.
        Used when the fused step cannot run (engine failure) so a lane is
        never left with host indexes ahead of its log."""
        node = self.node
        try:
            if self.fresh:
                node._merge_batch(self.ops, self.fresh)
            self._finish_recording()
        finally:
            self.done = True
            node._lock.release()
        return self.fresh + self.adopted

    def abort(self) -> None:
        """Release the node lock WITHOUT merging.  Only for process-fatal
        unwind: if fresh ops were accepted, the host indexes are ahead of
        the log until a later merge lands them (prefer commit_inline)."""
        self.done = True
        self.node._lock.release()

    def _finish_recording(self) -> None:
        node = self.node
        if self.births and node.recorder.enabled:
            node.recorder.note_births(self.births)
        if not self.recording:
            return
        vv_after = node._version_vector_locked()
        if vv_after == self.vv_before:
            return
        epoch = node.clock.epoch_ms
        cmds = None
        if node.recorder.tenant_of is not None:
            cmds = {(rid, seq): cmd for _, rid, seq, cmd in self.rows}
        node.recorder.note_visible(
            self.vv_before, vv_after,
            births={(rid, seq): ts + epoch
                    for ts, rid, seq, _ in self.rows},
            cmds=cmds,
        )


class ReplicaNode:
    def __init__(
        self,
        rid: int,
        capacity: int = 1024,
        clock: Optional[HostClock] = None,
        metrics: Optional[Metrics] = None,
        use_native: Optional[bool] = None,
        go_compat_gossip: bool = False,
        events: Optional[EventLog] = None,
    ):
        from crdt_tpu import native

        self.rid = rid
        # per-node structured event log (bounded ring; NodeHost attaches a
        # JSONL file sink for the cross-process forensic record)
        self.events = events if events is not None else EventLog(node=str(rid))
        # Opt-in MIXED-FLEET mode (round-2 verdict, missing #1): emit
        # full-dump gossip with the reference's BARE integer-ms keys so an
        # original Go peer can pull from this node without its Atoi loop
        # dying (/root/reference/main.go:251-254, quirk §0.1.8).
        # Documented lossiness: ops sharing a millisecond collapse to the
        # LAST writer's command per ms (highest (rid, seq) wins — the
        # deterministic analogue of the reference's own treemap-Put
        # overwrite, quirk §0.1.2).  crdt_tpu peers in such a fleet must
        # keep delta_gossip=True (delta payloads stay in native format);
        # compaction is forbidden (summary sections are not Go-parseable —
        # compact() raises).
        self.go_compat_gossip = bool(go_compat_gossip)
        self.clock = clock or HostClock()
        self.metrics = metrics or Metrics()
        # convergence flight recorder (crdt_tpu.obs.provenance): birth
        # stamps on the write path, vv-delta visibility on the merge path.
        # Enablement rides registry.enabled, so a NULL_REGISTRY node pays
        # nothing; drivers install a shared BirthLedger + step clock via
        # recorder.install (the soak harnesses / NodeHost do)
        self.recorder = FlightRecorder(
            rid, self.metrics.registry, events=self.events
        )
        if self.events.registry is None:
            # ring-eviction accounting (crdt_events_dropped_total) lands
            # in this node's registry unless the log already has a sink
            self.events.registry = self.metrics.registry
        # native C++ interner + batch packer when built (identical semantics,
        # tests/test_native.py); pure-Python otherwise
        self._native = native.AVAILABLE if use_native is None else use_native
        if self._native:
            self.keys = native.NativeInterner()
            self.values = native.NativeInterner()
            self._packer = native.OpBatchPacker(self.keys, self.values)
            # native mirror of the command map: gossip payload JSON is
            # emitted in C++ straight from the interner arenas
            self._wire = native.WireStore(self.keys, self.values)
        else:
            self.keys = Interner()
            self.values = Interner()
            self._packer = None
            self._wire = None
        self.log = oplog.empty(capacity)
        # host-tracked live row count of self.log, or None when unknown
        # (post-compaction): lets the batched write path skip a jitted
        # oplog.size dispatch + host sync per drain
        self._log_rows: Optional[int] = 0
        # extra metric labels for this plane's merge accounting (the
        # sharded keyspace binds {"shard": i}).  The label-free counters
        # keep their one-tick-per-DEVICE-dispatch meaning; when labels
        # are bound, merge_dispatches{shard=..} / union_path{shard=..}
        # additionally tick once per FOLDED LANE — so per-shard
        # attribution survives the mesh plane's fusion, which collapses
        # S lane folds into one device dispatch (parallel.meshplane).
        self._metric_labels: Dict[str, str] = {}
        # write-behind appends for the native wire cache: the batched
        # ingest drain queues (ts_abs, rid, seq, kids, vids) rows here and
        # every _wire reader drains via _flush_wire_locked — the per-op
        # native calls move off the admission hot path onto the (per-
        # gossip-round) serving path
        self._wire_pending: List[Tuple[int, int, int, list, list]] = []
        self.alive = True
        self._seq = SeqGen()
        self._lock = threading.Lock()
        # host copy of raw commands per op, for gossip serving:
        # (ts, rid, seq) -> {key: value}
        self._commands: Dict[Tuple[int, int, int], Dict[str, str]] = {}
        # delta-extraction indexes over _commands (share the same cmd dicts):
        # per-writer ops in ascending-seq order (seqs are per-writer
        # contiguous, so "ops after seq s" is a list slice — delta gossip
        # costs O(delta), not O(total history)), plus watermarkless rid<0
        # (Go-peer) ops, plus the incremental received watermark.
        self._by_writer: Dict[int, List[Tuple[Tuple[int, int, int], Dict[str, str]]]] = {}
        self._foreign: List[Tuple[Tuple[int, int, int], Dict[str, str]]] = []
        self._vv: Dict[int, int] = {}
        # go-compat echo dedup: ops round-tripping through a Go peer come
        # back with their identity flattened to the bare ts (rid=-1).  In
        # go-compat mode op identity therefore degrades to the reference's
        # own ts-identity for FOREIGN rows: a rid<0 op whose ts any held op
        # already occupies is a re-echo (or a same-ms collision, which the
        # mode's last-writer-per-ms rule already declares lossy) and is
        # dropped — the reference's local-wins rule, quirk §0.1.2.
        self._ts_seen: set = set()
        # compaction state (crdt_tpu.models.compactlog): per-writer folded
        # watermark + the per-key fold of everything under it.  Summary
        # entries are wire-shaped: {"num", "num_count", "ts" (absolute ms),
        # "rid", "seq", "payload" (raw string), "is_num"}.
        self._frontier: Dict[int, int] = {}
        self._summary: Dict[str, Dict[str, Any]] = {}
        # encoded-summary cache: (Summary arrays, key-space size) — the host
        # summary only changes on compact/adopt, but get_state() needs it as
        # device arrays every call
        self._summary_cache: Optional[Tuple[compactlog.Summary, int]] = None
        # live divergence audit plane (crdt_tpu.obs.audit): incremental
        # winner-row digest, opt-in via enable_audit() — bare nodes pay
        # one `is not None` check on the ingest hot paths
        self.digest = None

    # ---- write path ----

    def add_command(self, cmd: Dict[str, str], ts: Optional[int] = None) -> bool:
        """POST /data: append one multi-key command.  Returns False when the
        node is down (the reference 502s, main.go:210-212)."""
        with self._lock:
            if not self.alive:
                return False
            ts = self.clock.now_ms() if ts is None else ts
            if not (0 <= ts < INT32_MAX):
                # ts == INT32_MAX IS the SENTINEL padding encoding: a row
                # minted there would be invisible to every sorted-table
                # path (silent data loss).  ~24.8 days of epoch offset —
                # restart (or re-epoch) the node before then, loudly.
                raise ValueError(
                    f"local timestamp {ts} outside the storable int32 "
                    f"window [0, {INT32_MAX}) (ts == {INT32_MAX} is the "
                    "SENTINEL padding encoding)"
                )
            seq = self._seq.next()
            with self.metrics.timer("write"):
                self._ingest([(ts, self.rid, seq, dict(cmd))])
            if self.recorder.enabled:
                # birth record (origin, seq, birth_step): the wire ts IS
                # the op's absolute-ms birth timestamp every observer sees
                self.recorder.note_birth(seq, ts + self.clock.epoch_ms)
            return True

    def add_commands(
        self,
        cmds: List[Dict[str, str]],
        tss: Optional[List[Optional[int]]] = None,
    ) -> Optional[List[Tuple[int, int]]]:
        """Batched write path (the ingest admission drain): mint seqs for
        every command and land them all in ONE jitted ingest dispatch —
        the write-side analogue of ``receive_many``.  ``tss[i]`` (None =
        stamp now) must satisfy the same int32 window as add_command.
        Returns the minted (rid, seq) idents in submission order, or
        None when the node is down (every op in the drain 502s whole —
        same all-or-nothing the single-op route has).

        Unlike add_command, the command dicts are adopted WITHOUT a
        defensive copy and must not be mutated after the call: op pages
        deliberately share one dict per distinct (key, value) pair
        (OpPage.rows), and copying would both defeat that dedup and put
        an allocation per op back on the hot path."""
        with self._lock:
            if not self.alive:
                return None
            if not cmds:
                return []
            n = len(cmds)
            if tss is None:
                now = self.clock.now_ms()
                tss = [now] * n
            else:
                if len(tss) != n:
                    raise ValueError(
                        f"{len(tss)} timestamps for {n} commands")
                if None in tss:
                    now = self.clock.now_ms()
                    tss = [now if t is None else t for t in tss]
            # validate the whole batch BEFORE any bookkeeping mutates
            # (all-or-nothing, same as the single-op route); min/max scan
            # the list at C speed — the per-op check only runs to name
            # the offender once a violation is known to exist
            if not (0 <= min(tss) and max(tss) < INT32_MAX):
                i, ts = next((i, t) for i, t in enumerate(tss)
                             if not (0 <= t < INT32_MAX))
                raise ValueError(
                    f"batch op {i}: timestamp {ts} outside the storable "
                    f"int32 window [0, {INT32_MAX}) (ts == {INT32_MAX} "
                    "is the SENTINEL padding encoding)"
                )
            seq0 = self._seq.reserve(n)
            with self.metrics.timer("write"):
                self._ingest_local_batch(cmds, tss, seq0)  # one dispatch
            if self.recorder.enabled:
                epoch = self.clock.epoch_ms
                self.recorder.note_births(
                    [(seq0 + i, t + epoch) for i, t in enumerate(tss)])
            rid = self.rid
            return [(rid, seq0 + i) for i in range(n)]

    # ---- read path ----

    def get_state(self) -> Optional[Dict[str, str]]:
        """GET /data: the materialized key-value view (None when down)."""
        if not self.alive:
            return None
        with self._lock:
            if self._frontier:
                kv = compactlog.rebuild(self._device_clog_locked())
            else:
                kv = oplog.rebuild(self.log, n_keys=self._n_keys())
            return oplog.materialize(kv, self.keys, self.values)

    # round array dims up to powers of two: jit shapes are static, so this
    # bounds recompiles to O(log n) instead of one per newly-interned key /
    # newly-seen writer (materialize only reads len(keys))
    def _n_keys(self) -> int:
        n = 16
        while n < len(self.keys):
            n *= 2
        return n

    def _n_writers(self) -> int:
        top = max([self.rid, *self._frontier, *self._vv], default=0)
        n = 8
        while n <= top:
            n *= 2
        return n

    # ---- gossip ----

    def version_vector(self) -> Dict[int, int]:
        """This node's received watermark: writer rid -> max contiguous seq
        held (folded or raw).  The delta-gossip request token."""
        with self._lock:
            return self._version_vector_locked()

    def vv_snapshot(self) -> Tuple[Dict[int, int], Dict[int, int]]:
        """(version vector, folded frontier) under ONE lock acquisition —
        barrier coordinators need the pair to be mutually consistent (a
        frontier adopted between two separate reads would report a frontier
        ahead of the vv and spuriously fail the chain-rule check)."""
        with self._lock:
            return self._version_vector_locked(), dict(self._frontier)

    @property
    def frontier(self) -> Dict[int, int]:
        """This node's folded watermark (snapshot copy)."""
        with self._lock:
            return dict(self._frontier)

    def _version_vector_locked(self) -> Dict[int, int]:
        vv = dict(self._frontier)
        for rid, seq in self._vv.items():
            if seq > vv.get(rid, -1):
                vv[rid] = seq
        return vv

    def gossip_payload(
        self, since: Optional[Dict[int, int]] = None
    ) -> Optional[Dict[str, Any]]:
        """GET /gossip: op-log wire JSON (None when down — caller skips,
        mirroring the 502 path main.go:166-169).

        ``since`` is the requester's version vector: only ops it is missing
        are included (delta gossip — the reference re-ships its ENTIRE log
        every round, main.go:159).  When this node has compacted past what
        ``since`` covers, the payload additionally carries the summary +
        frontier sections so the requester can adopt the fold.

        Wire-compat notes: (1) rid<0 (Go-format) ops carry no watermark and
        are re-shipped in every payload — delta extraction is O(delta) only
        over native ops, so mixed fleets lose the payload bound for the
        foreign part (receivers dedup them; `receive` reports 0 fresh ops);
        (2) ``since=None`` returns every *retained* raw op, which is the
        reference's full-log dump only while this node has never compacted —
        after a fold the payload necessarily includes the reserved sections,
        which a Go peer cannot parse (ClusterConfig.compact_every documents
        the mixed-fleet rule: don't compact).
        """
        if not self.alive:
            return None
        with self._lock:
            return self._payload_locked(since)

    def _needs_sections_locked(self, since: Optional[Dict[int, int]]) -> bool:
        """Must the payload carry the __frontier__/__summary__ sections?
        (Yes when this node has folded past what ``since`` covers.)"""
        since = since or {}
        return bool(self._frontier) and not all(
            since.get(r, -1) >= s for r, s in self._frontier.items()
        )

    def _payload_locked(self, since: Optional[Dict[int, int]]) -> Dict[str, Any]:
        epoch = self.clock.epoch_ms
        if since is None:
            if self.go_compat_gossip:
                # reference-format full dump: bare integer-ms keys a Go
                # peer's Atoi loop parses (main.go:251-254).  Iteration is
                # (ts, rid, seq)-ascending, so same-ms ops collapse to the
                # highest (rid, seq) — last-writer-per-ms, documented
                # lossiness mirroring the reference's own treemap-Put
                # collision rule (quirk §0.1.2)
                return {
                    str(k[0] + epoch): dict(v)
                    for k, v in sorted(self._commands.items())
                }
            # full dump of retained raw ops, ts-sorted like the
            # reference's treemap JSON (main.go:159); Go-compatible only
            # while this node has never compacted (see docstring)
            payload: Dict[str, Any] = {
                _wire_key(k[0] + epoch, k[1], k[2]): dict(v)
                for k, v in sorted(self._commands.items())
            }
        else:
            # delta: per-writer tail slices — O(|delta|), not O(history)
            payload = {
                _wire_key(k[0] + epoch, k[1], k[2]): dict(v)
                for k, v in self._foreign
            }
            for w, lst in self._by_writer.items():
                if not lst:
                    continue
                start = since.get(w, -1) + 1 - lst[0][0][2]
                for k, v in lst[max(start, 0):]:
                    payload[_wire_key(k[0] + epoch, k[1], k[2])] = dict(v)
        if self._frontier:
            # the frontier piggybacks on EVERY payload (eager pruning: a
            # caught-up requester folds + prunes at adoption time from its
            # own raw ops — _adopt_frontier_locked's local-fold branch);
            # the summary sections ride along only when the requester is
            # behind the fold and needs them to reconstruct state
            payload[FRONTIER_KEY] = {
                str(r): s for r, s in self._frontier.items()
            }
            if self._needs_sections_locked(since):
                payload[SUMMARY_KEY] = {
                    k: dict(e) for k, e in self._summary.items()
                }
        return payload

    def gossip_payload_json(
        self, since: Optional[Dict[int, int]] = None
    ) -> Optional[bytes]:
        """``gossip_payload`` pre-serialized to UTF-8 JSON bytes — the HTTP
        serving path.  When the native runtime is up and no compaction
        sections are needed, the bytes are emitted by the C++ wire store
        (one pass over the op map, zero Python dict/string churn);
        otherwise json.dumps of the Python payload, under the SAME lock
        acquisition (one consistent snapshot either way)."""
        if not self.alive:
            return None
        with self._lock:
            if self._wire is not None and not self._frontier \
                    and not (self.go_compat_gossip and since is None):
                # (the C++ emitter writes native ts:rid:seq keys and no
                # frontier/summary sections, so any folded node serves via
                # the Python path; go-compat full dumps likewise)
                self._flush_wire_locked()
                return self._wire.payload_json(since)
            payload = self._payload_locked(since)
        return json.dumps(payload).encode()

    def _decode_payload(self, payload: Dict[str, Any]):
        """Wire payload -> (remote_frontier, remote_summary, op rows),
        timestamps rebased onto this node's int32 window.  A malformed key
        raises ValueError (the reference silently killed its gossip loop
        forever, quirk §0.1.8 — failing loudly is the fix)."""
        payload = dict(payload)
        remote_frontier = {
            int(r): int(s)
            for r, s in (payload.pop(FRONTIER_KEY, None) or {}).items()
        }
        remote_summary = payload.pop(SUMMARY_KEY, None) or {}
        epoch = self.clock.epoch_ms
        rows = []
        for k, cmd in payload.items():
            ts_abs, rid, seq = _parse_wire_key(k)
            ts = ts_abs - epoch  # rebase onto this node's int32 window
            # strict upper bound: ts == INT32_MAX is the SENTINEL padding
            # encoding — a row stored there would silently read as a hole
            if not (INT32_MIN <= ts < INT32_MAX):
                raise ValueError(
                    f"gossip timestamp {ts_abs} is outside this node's int32 "
                    f"window (epoch {epoch}); reference quirk §0.1.8 made this "
                    "kill gossip silently — here it fails loudly"
                )
            rows.append((ts, rid, seq, cmd))
        return remote_frontier, remote_summary, rows

    def validate_payload(self, payload: Dict[str, Any]) -> Optional[str]:
        """Structural pre-check of a wire payload WITHOUT merging: returns
        None when ``receive`` would accept it, else a short reason string.
        The fused pull path uses this to quarantine ONE malformed payload
        (byte-corrupted body that still parsed as JSON, mangled wire key,
        out-of-window timestamp, non-dict command) without poisoning the
        other k-1 payloads sharing its merge dispatch."""
        try:
            _, summary, rows = self._decode_payload(dict(payload))
        except (ValueError, KeyError, TypeError, AttributeError) as e:
            return f"{type(e).__name__}: {e}"
        for _, _, _, cmd in rows:
            if not isinstance(cmd, dict):
                return f"non-dict command: {type(cmd).__name__}"
        for k, entry in summary.items():
            if not isinstance(entry, dict):
                return f"non-dict summary entry for key {k!r}"
        return None

    def receive(self, payload: Optional[Dict[str, Any]]) -> int:
        """Pull-side merge of a peer's gossip payload (main.go:250-257);
        returns the number of genuinely new ops absorbed (0 = the payload
        taught us nothing — re-deliveries and already-folded ops dedup).
        Unknown strings are interned locally."""
        if not payload or not self.alive:
            return 0
        remote_frontier, remote_summary, rows = self._decode_payload(payload)
        recording = self.recorder.enabled
        vv_before = vv_after = None
        with self._lock:
            with self.metrics.timer("merge"), span("crdt.merge"):
                if recording:
                    vv_before = self._version_vector_locked()
                adopted = 0
                if remote_frontier:
                    adopted = self._adopt_frontier_locked(
                        remote_frontier, remote_summary
                    )
                fresh = self._ingest(rows)
                if recording:
                    vv_after = self._version_vector_locked()
        if recording and vv_after != vv_before:
            # newly-visible origin-seq ranges fall out of the vv delta —
            # no per-op scan; duplicate/reordered deliveries (vv did not
            # move) emit nothing, so exactly-once holds structurally
            epoch = self.clock.epoch_ms
            cmds = None
            if self.recorder.tenant_of is not None:
                # tenant attribution (keyspace shards): hand the recorder
                # the raw command rows so it can read each op's tenant
                cmds = {(rid, seq): cmd for _, rid, seq, cmd in rows}
            self.recorder.note_visible(
                vv_before, vv_after,
                births={(rid, seq): ts + epoch for ts, rid, seq, _ in rows},
                cmds=cmds,
            )
        return fresh + adopted

    def receive_many(self, payloads: List[Dict[str, Any]]) -> int:
        """K-way FUSED merge: absorb several peers' gossip payloads in ONE
        device merge dispatch (the pipelined merge runtime's pull-side; see
        :func:`fused_pull_round`).

        Bit-exact against merging the payloads one ``receive`` at a time in
        any order: the op union is ACI (identical idents dedup in _accept_locked,
        the ingest batch is canonically re-sorted by from_ops/merge), and
        compaction frontiers on a correctly-deployed fleet form a chain, so
        adopting them in payload order lands on the same maximal fold.  The
        fusion only changes HOW MANY device dispatches the round costs:
        one ``_ingest`` (one sorted-union dispatch) for all P payloads
        instead of P.
        """
        if not self.alive:
            return 0
        decoded = [
            self._decode_payload(p) for p in payloads if p
        ]
        if not decoded:
            return 0
        recording = self.recorder.enabled
        vv_before = vv_after = None
        with self._lock:
            with self.metrics.timer("merge"), span("crdt.merge_fused"):
                if recording:
                    vv_before = self._version_vector_locked()
                adopted = 0
                rows_all: List[Tuple[int, int, int, Dict[str, str]]] = []
                for remote_frontier, remote_summary, rows in decoded:
                    if remote_frontier:
                        adopted += self._adopt_frontier_locked(
                            remote_frontier, remote_summary
                        )
                    rows_all.extend(rows)
                fresh = self._ingest(rows_all)
                if recording:
                    vv_after = self._version_vector_locked()
        if recording and vv_after != vv_before:
            # one vv delta covers the whole fused round: per (origin, seq)
            # the k payloads' duplicates collapse to one visibility
            epoch = self.clock.epoch_ms
            cmds = None
            if self.recorder.tenant_of is not None:
                cmds = {(rid, seq): cmd for _, rid, seq, cmd in rows_all}
            self.recorder.note_visible(
                vv_before, vv_after,
                births={(rid, seq): ts + epoch
                        for ts, rid, seq, _ in rows_all},
                cmds=cmds,
            )
        return fresh + adopted

    # ---- deferred merge (the device-mesh plane's entry points) ----

    def merge_begin(self, payloads: List[Dict[str, Any]]) -> PendingMerge:
        """Deferred-merge half of :meth:`receive_many`: decode + adopt
        frontiers + accept + pack ``payloads`` exactly like the inline
        path, but STOP before the device dispatch and return the packed
        batch with the node lock HELD.  The mesh plane
        (crdt_tpu.parallel.meshplane.MeshPlane) folds many planes'
        pending batches in ONE fused dispatch, then calls
        :meth:`PendingMerge.commit` (or ``commit_inline`` on engine
        failure) on each.  Never call from a thread already holding this
        node's lock; an empty ``payloads`` still returns a (zero-fresh)
        pending so the caller's lane layout stays static."""
        decoded = [self._decode_payload(p) for p in payloads if p]
        pending = PendingMerge(self)
        self._lock.acquire()
        try:
            pending.recording = self.recorder.enabled
            if pending.recording:
                pending.vv_before = self._version_vector_locked()
            if self.alive and decoded:
                rows_all: List[Tuple[int, int, int, Dict[str, str]]] = []
                for remote_frontier, remote_summary, rows in decoded:
                    if remote_frontier:
                        pending.adopted += self._adopt_frontier_locked(
                            remote_frontier, remote_summary
                        )
                    rows_all.extend(rows)
                pending.rows = rows_all
                accepted = self._accept_locked(rows_all)
                pending.ops, pending.fresh = self._pack_accepted_locked(
                    accepted)
                if pending.fresh and self.digest is not None \
                        and self.digest.enabled:
                    pending.dig = self.digest.dig_column(
                        accepted, self.clock.epoch_ms)
                    pending.dig_sum = pending.dig.sum(
                        axis=0, dtype=np.uint32)
        except BaseException:
            self._lock.release()
            raise
        return pending

    def add_commands_begin(
        self,
        cmds: List[Dict[str, str]],
        tss: Optional[List[Optional[int]]] = None,
    ) -> Tuple[Optional[List[Tuple[int, int]]], PendingMerge]:
        """Deferred-merge half of :meth:`add_commands` (the fused keyspace
        drain): mint seqs and do every piece of host bookkeeping, but
        leave the device merge to the mesh plane.  Returns ``(idents,
        pending)`` with the node lock HELD inside ``pending``; idents is
        None when the node is down (the pending is then zero-fresh and
        must still be committed/aborted to release the lock)."""
        pending = PendingMerge(self)
        self._lock.acquire()
        try:
            if not self.alive:
                return None, pending
            if not cmds:
                return [], pending
            n = len(cmds)
            if tss is None:
                now = self.clock.now_ms()
                tss = [now] * n
            else:
                if len(tss) != n:
                    raise ValueError(
                        f"{len(tss)} timestamps for {n} commands")
                if None in tss:
                    now = self.clock.now_ms()
                    tss = [now if t is None else t for t in tss]
            if not (0 <= min(tss) and max(tss) < INT32_MAX):
                i, ts = next((i, t) for i, t in enumerate(tss)
                             if not (0 <= t < INT32_MAX))
                raise ValueError(
                    f"batch op {i}: timestamp {ts} outside the storable "
                    f"int32 window [0, {INT32_MAX}) (ts == {INT32_MAX} "
                    "is the SENTINEL padding encoding)"
                )
            seq0 = self._seq.reserve(n)
            pending.ops, pending.fresh = self._pack_local_batch(
                cmds, tss, seq0)
            epoch = self.clock.epoch_ms
            if pending.fresh and self.digest is not None \
                    and self.digest.enabled:
                pending.dig = self.digest.dig_column(
                    [(t, self.rid, seq0 + i, c)
                     for i, (c, t) in enumerate(zip(cmds, tss))],
                    epoch)
                pending.dig_sum = pending.dig.sum(axis=0, dtype=np.uint32)
            pending.births = [(seq0 + i, t + epoch)
                              for i, t in enumerate(tss)]
            rid = self.rid
            return [(rid, seq0 + i) for i in range(n)], pending
        except BaseException:
            self._lock.release()
            raise

    # ---- live divergence audit (crdt_tpu.obs.audit) ----

    def enable_audit(self, plane: str = "host"):
        """Opt in to the live divergence audit plane: attach an
        incremental winner-row digest (crdt_tpu.obs.audit.PlaneDigest)
        and seed it from the current store.  Idempotent (re-labels +
        reseeds); returns the digest.  Enablement additionally rides
        ``metrics.registry.enabled``, so a NULL_REGISTRY node stays
        digest-free even after this call."""
        from crdt_tpu.obs.audit import PlaneDigest

        with self._lock:
            if self.digest is None:
                self.digest = PlaneDigest(self, plane=plane)
            else:
                self.digest.plane = plane
            self.digest.resync()
        return self.digest

    def audit_digest_at(self, frontier: Dict[int, int]) -> Optional[str]:
        """Hex digest of this node's state clamped at ``frontier``, or
        None when the clamp is not comparable here: the digest below F is
        well-defined only while this node's own compaction frontier <= F
        (folded non-winner candidates under our fold are gone) and
        F <= our vv (we have actually seen everything under F).  Inside
        that window the below-F winner set is immutable, so the result
        is independent of in-flight ops and delivery order."""
        with self._lock:
            d = self.digest
            if d is None or not d.enabled:
                return None
            frontier = {int(r): int(s) for r, s in frontier.items()}
            if not all(frontier.get(r, -1) >= s
                       for r, s in self._frontier.items()):
                return None
            vv = self._version_vector_locked()
            if not all(s <= vv.get(r, -1) for r, s in frontier.items()):
                return None
            return d.digest_hex_at(frontier)

    def audit_snapshot(self) -> Tuple[Dict[int, int], Dict[int, int],
                                      Optional[str]]:
        """One-lock (vv, frontier, digest-at-frontier-hex) snapshot — the
        gossip piggyback source (api.http_shim): the digest MUST be
        clamped at the same frontier the stability summary carries, so
        the three travel as one atomic read."""
        with self._lock:
            vv = self._version_vector_locked()
            frontier = dict(self._frontier)
            d = self.digest
            dig = (d.digest_hex_at(frontier)
                   if d is not None and d.enabled else None)
        return vv, frontier, dig

    def audit_scrub(self) -> bool:
        """Recompute the digest FROM the store and adopt it; True when
        the accumulator disagreed (the store changed underneath the
        digest — silent corruption entering the served digest)."""
        with self._lock:
            d = self.digest
            if d is None or not d.enabled:
                return False
            return d.scrub()

    def _digest_resync_locked(self) -> None:
        if self.digest is not None and self.digest.enabled:
            self.digest.resync()

    # ---- health / fault injection ----

    def ping(self) -> bool:
        return self.alive

    def set_alive(self, alive: bool) -> None:
        self.alive = bool(alive)

    # ---- compaction (delta-CRDT log pruning, crdt_tpu.models.compactlog) ----

    def compact(self, frontier: Dict[int, int]) -> None:
        """Fold every held op at or under ``frontier`` into the summary and
        prune it from the log + command map.

        ``frontier`` must be swarm-stable (LocalCluster.compact computes the
        min over alive nodes' version vectors); like the device path it is
        clamped to this node's own knowledge, so a too-eager frontier cannot
        drop never-received ops.  The fold itself runs on-device
        (compactlog.compact) and is decoded back to the wire-shaped host
        summary — one semantics, two representations.
        """
        if self.go_compat_gossip:
            raise ValueError(
                "compaction is forbidden in go-compat gossip mode: a folded "
                "node's payload needs the __summary__ sections, which a Go "
                "peer cannot parse (its gossip loop would die, quirk §0.1.8)"
            )
        with self._lock:
            vv = self._version_vector_locked()
            target = {
                r: min(s, vv.get(r, -1))
                for r, s in frontier.items()
            }
            target = {
                r: s
                for r, s in target.items()
                if s > self._frontier.get(r, -1)
            }
            if not target:
                return
            merged = dict(self._frontier)
            merged.update(target)
            with span("crdt.compact") as tid:
                self._compact_to_locked(merged)
                self.metrics.inc("compactions")
                self.events.emit("compact", trace=tid,
                                 frontier={str(r): s for r, s in merged.items()})

    def _compact_to_locked(self, merged: Dict[int, int]) -> None:
        """On-device fold to ``merged`` + host pruning (caller holds the
        lock and has already clamped ``merged`` to this node's vv and
        checked it advances the current frontier).  Shared by explicit
        :meth:`compact` and the adoption-time local fold in
        :meth:`_adopt_frontier_locked` — the caller owns the counter/event
        so "compactions" keeps meaning explicit folds only."""
        w = self._n_writers()
        folded = compactlog.compact(
            self._device_clog_locked(n_writers=w),
            self._frontier_array(merged, w),
        )
        self.log = folded.tail
        self._log_rows = None
        self._frontier = merged
        self._summary = self._decode_summary(folded.summary)
        self._summary_cache = (
            folded.summary, folded.summary.num.shape[-1]
        )
        self._prune_commands_locked()
        # the fold rewrote the store wholesale — rebuild the audit digest
        # from it (O(state) exactly where an O(state) rewrite already is)
        self._digest_resync_locked()

    def _adopt_frontier_locked(
        self, remote_frontier: Dict[int, int], remote_summary: Dict[str, Any]
    ) -> int:
        """Adopt a further-ahead peer's fold (the chain rule of
        compactlog.merge on the wire); returns 1 if the frontier advanced.
        Frontiers advance only through swarm-stable barriers, so two live
        frontiers are always comparable; incomparable ones mean a
        mis-deployed cluster and fail loudly."""
        rids = set(self._frontier) | set(remote_frontier)
        own_geq = all(
            self._frontier.get(r, -1) >= remote_frontier.get(r, -1)
            for r in rids
        )
        if own_geq:
            return 0  # our fold covers theirs; their ops filter via _ingest
        remote_geq = all(
            remote_frontier.get(r, -1) >= self._frontier.get(r, -1)
            for r in rids
        )
        if not remote_geq:
            raise ValueError(
                f"incomparable compaction frontiers (ours {self._frontier}, "
                f"remote {remote_frontier}): frontiers must advance through "
                "swarm-stable barriers (chain rule)"
            )
        if all(s <= self._vv.get(r, -1) for r, s in remote_frontier.items()):
            # Our raw ops already cover the remote fold, so fold LOCALLY
            # instead of adopting the wire summary: a deterministic fold
            # over identical per-writer prefixes is bit-identical to the
            # peer's.  This is what lets the frontier piggyback on EVERY
            # payload without shipping summary sections — a caught-up node
            # drops its _commands/_by_writer slices below the stable
            # frontier at adoption time (eager pruning) instead of holding
            # them until its own compact() call.
            merged = dict(self._frontier)
            merged.update(remote_frontier)
            self._compact_to_locked(merged)
            self.metrics.inc("frontier_adoptions")
            self.events.emit(
                "frontier_adopt", trace=current_trace(),
                frontier={str(r): s for r, s in self._frontier.items()},
            )
            return 1
        # A non-trivial frontier always folds >=1 op, and every folded op
        # contributes a key — an empty/missing summary can only mean a
        # truncated or corrupted payload.  Adopting it would silently destroy
        # the folded state (prune below), so fail loudly instead.
        if any(s >= 0 for s in remote_frontier.values()) and not remote_summary:
            raise ValueError(
                f"frontier {remote_frontier} arrived with an empty/missing "
                "__summary__ section: refusing to adopt (truncated payload?)"
            )
        self._summary = {
            str(k): _summary_entry(e) for k, e in remote_summary.items()
        }
        self._frontier = dict(remote_frontier)
        self._summary_cache = None
        for r, s in remote_frontier.items():  # summary extends our knowledge
            if s > self._vv.get(r, -1):
                self._vv[r] = s
        # drop now-folded raw rows (they are accounted in the adopted summary)
        w = self._n_writers()
        self.log = oplog.delta_since(
            self.log, self._frontier_array(self._frontier, w)
        )
        self._log_rows = None
        self._prune_commands_locked()
        self._digest_resync_locked()  # the adopted summary replaced ours
        self.metrics.inc("frontier_adoptions")
        self.events.emit(
            "frontier_adopt", trace=current_trace(),
            frontier={str(r): s for r, s in self._frontier.items()},
        )
        return 1

    def _prune_commands_locked(self) -> None:
        f = self._frontier
        kept = {
            k: v
            for k, v in self._commands.items()
            if not (k[1] >= 0 and k[2] <= f.get(k[1], -1))
        }
        if self._wire is not None:
            self._flush_wire_locked()  # removals must see deferred adds
            epoch = self.clock.epoch_ms
            for k in self._commands.keys() - kept.keys():
                self._wire.remove(k[0] + epoch, k[1], k[2])
        reclaimed = len(self._commands) - len(kept)
        if reclaimed:
            # ops actually freed by this fold/adoption — the GC payoff
            # counter behind crdt_gc_reclaimed_ops_total (obs/health.py)
            self.metrics.inc("gc_reclaimed_ops", reclaimed)
        self._commands = kept
        for w, lst in self._by_writer.items():
            cut = f.get(w, -1)
            if lst and lst[0][0][2] <= cut:
                self._by_writer[w] = [e for e in lst if e[0][2] > cut]

    def _rebuild_indexes_locked(self) -> None:
        """Recompute the delta indexes from _commands + frontier (snapshot
        restore path, crdt_tpu.utils.checkpoint.restore_node)."""
        self._by_writer = {}
        self._foreign = []
        self._vv = {}
        self._ts_seen = (
            {k[0] for k in self._commands} if self.go_compat_gossip else set()
        )
        self._summary_cache = None
        if self._wire is not None:
            from crdt_tpu import native

            # pending rows are already in _commands: the rebuild re-adds
            # them, so the write-behind queue just resets
            self._wire_pending.clear()
            self._wire = native.WireStore(self.keys, self.values)
            epoch = self.clock.epoch_ms
            for (ts, rid, seq), cmd in self._commands.items():
                self._wire.add(ts + epoch, rid, seq, cmd)
        for ident in sorted(self._commands, key=lambda k: (k[1], k[2], k[0])):
            stored = self._commands[ident]
            rid, seq = ident[1], ident[2]
            if rid >= 0:
                self._by_writer.setdefault(rid, []).append((ident, stored))
                if seq > self._vv.get(rid, -1):
                    self._vv[rid] = seq
            else:
                self._foreign.append((ident, stored))
        for r, s in self._frontier.items():
            if s > self._vv.get(r, -1):
                self._vv[r] = s
        self._digest_resync_locked()  # restore path: reseed from store

    def _frontier_array(self, frontier: Dict[int, int], n_writers: int):
        import jax.numpy as jnp

        arr = np.full((n_writers,), -1, np.int32)
        for r, s in frontier.items():
            if 0 <= r < n_writers:
                arr[r] = s
        return jnp.asarray(arr)

    def _device_clog_locked(self, n_writers: Optional[int] = None) -> compactlog.CompactedLog:
        """The device view of this node's full state: host summary + frontier
        encoded as arrays over the current interned key space, tail = log."""
        import jax.numpy as jnp

        # intern summary strings BEFORE sizing the key space: an adopted
        # summary can mention keys this node never saw as raw ops
        for key_str, e in self._summary.items():
            self.keys.intern(key_str)
            self.values.intern(e["payload"])
        k = self._n_keys()
        w = n_writers or self._n_writers()
        epoch = self.clock.epoch_ms
        if self._summary_cache is not None and self._summary_cache[1] == k:
            return compactlog.CompactedLog(
                summary=self._summary_cache[0],
                frontier=self._frontier_array(self._frontier, w),
                tail=self.log,
            )
        s = compactlog.empty_summary(k)
        if self._summary:
            cols = {
                n: np.array(getattr(s, n))  # np.array: writable copy
                for n in ("present", "num", "num_count", "ts", "rid", "seq",
                          "payload", "is_num")
            }
            for key_str, e in self._summary.items():
                i = self.keys.intern(key_str)
                ts = e["ts"] - epoch
                if not (INT32_MIN <= ts <= INT32_MAX):
                    raise ValueError(
                        f"summary timestamp {e['ts']} outside this node's "
                        f"int32 window (epoch {epoch})"
                    )
                cols["present"][i] = True
                cols["num"][i] = e["num"]
                cols["num_count"][i] = e["num_count"]
                cols["ts"][i] = ts
                cols["rid"][i] = e["rid"]
                cols["seq"][i] = e["seq"]
                cols["payload"][i] = self.values.intern(e["payload"])
                cols["is_num"][i] = e["is_num"]
            s = compactlog.Summary(**{n: jnp.asarray(c) for n, c in cols.items()})
        self._summary_cache = (s, k)
        return compactlog.CompactedLog(
            summary=s,
            frontier=self._frontier_array(self._frontier, w),
            tail=self.log,
        )

    def _decode_summary(self, s: compactlog.Summary) -> Dict[str, Dict[str, Any]]:
        epoch = self.clock.epoch_ms
        present = np.asarray(s.present)
        num = np.asarray(s.num)
        num_count = np.asarray(s.num_count)
        ts = np.asarray(s.ts)
        rid = np.asarray(s.rid)
        seq = np.asarray(s.seq)
        payload = np.asarray(s.payload)
        is_num = np.asarray(s.is_num)
        out: Dict[str, Dict[str, Any]] = {}
        for i in range(len(self.keys)):
            if not present[i]:
                continue
            out[self.keys.lookup(i)] = _summary_entry({
                "num": num[i],
                "num_count": num_count[i],
                "ts": int(ts[i]) + epoch,
                "rid": rid[i],
                "seq": seq[i],
                "payload": self.values.lookup(int(payload[i])),
                "is_num": is_num[i],
            })
        return out

    # ---- internals ----

    def _accept_locked(self, rows) -> List[Tuple[int, int, int, Dict[str, str]]]:
        """Filter duplicate / already-folded rows, record the survivors in
        the command map + delta indexes, and return them.  Rows are taken in
        (rid, seq) order so each writer's index list stays seq-ascending
        (per-writer prefixes are contiguous, so a later batch's seqs always
        extend the list)."""
        accepted = []
        f = self._frontier
        for ts, rid, seq, cmd in sorted(rows, key=lambda r: (r[1], r[2], r[0])):
            ident = (ts, rid, seq)
            if ident in self._commands:
                continue  # duplicate op (gossip re-delivery): union no-op
            if rid >= 0 and seq <= f.get(rid, -1):
                continue  # already folded into the summary
            if self.go_compat_gossip and rid < 0 and ts in self._ts_seen:
                continue  # go-compat echo: ts-identity local-wins (§0.1.2)
            stored = dict(cmd)
            self._commands[ident] = stored
            if self.go_compat_gossip:
                self._ts_seen.add(ts)
            if self._wire is not None:
                self._wire.add(ts + self.clock.epoch_ms, rid, seq, stored)
            if rid >= 0:
                self._by_writer.setdefault(rid, []).append((ident, stored))
                if seq > self._vv.get(rid, -1):
                    self._vv[rid] = seq
            else:
                self._foreign.append((ident, stored))
            accepted.append((ts, rid, seq, stored))
        if accepted and self.digest is not None and self.digest.enabled:
            self.digest.observe_rows(accepted, self.clock.epoch_ms)
        return accepted

    def _pack_accepted_locked(
        self, accepted: List[Tuple[int, int, int, Dict[str, str]]]
    ) -> Tuple[Optional[Dict[str, np.ndarray]], int]:
        """Pack accepted rows into merge-ready op columns (caller holds the
        lock); returns ``(ops, fresh)`` with ``ops=None`` when nothing is
        fresh.  Shared by the inline ``_ingest`` path and the mesh plane's
        deferred :meth:`merge_begin`."""
        fresh = 0
        if self._packer is not None:  # native packing path
            for ts, rid, seq, cmd in accepted:
                for k, v in cmd.items():
                    self._packer.add(ts, rid, seq, k, v)
                    fresh += 1
            if not fresh:
                return None, 0
            return self._packer.take(), fresh
        cols = {n: [] for n in ("ts", "rid", "seq", "key", "val", "payload", "is_num")}
        for ts, rid, seq, cmd in accepted:
            for k, v in cmd.items():
                val, payload, is_num = encode_value(v, self.values)
                cols["ts"].append(ts)
                cols["rid"].append(rid)
                cols["seq"].append(seq)
                cols["key"].append(self.keys.intern(k))
                cols["val"].append(val)
                cols["payload"].append(payload)
                cols["is_num"].append(is_num)
                fresh += 1
        if not fresh:
            return None, 0
        ops = {
            n: np.asarray(c, bool if n == "is_num" else np.int32)
            for n, c in cols.items()
        }
        return ops, fresh

    def _ingest(self, rows: List[Tuple[int, int, int, Dict[str, str]]]) -> int:
        """Append/merge op rows (caller holds the lock); returns how many
        genuinely new ops landed.  Grows the log (2x) instead of silently
        dropping ops at capacity overflow."""
        ops, fresh = self._pack_accepted_locked(self._accept_locked(rows))
        if not fresh:
            return 0
        self._merge_batch(ops, fresh)
        return fresh

    def _ingest_local_batch(
        self, cmds: List[Dict[str, str]], tss: List[int], seq0: int
    ) -> int:
        ops, fresh = self._pack_local_batch(cmds, tss, seq0)
        if not fresh:  # all-empty commands: bookkeeping only, no dispatch
            return 0
        self._merge_batch(ops, fresh)
        return fresh

    def _pack_local_batch(
        self, cmds: List[Dict[str, str]], tss: List[int], seq0: int
    ) -> Tuple[Optional[Dict[str, np.ndarray]], int]:
        """The ingest admission drain's hot path (caller holds the lock):
        append locally-minted rows (cmds[i] at ts tss[i] with seq
        seq0 + i), already seq-ascending and fresh by construction, so
        _accept_locked's sort and duplicate/frontier checks are skipped.  Per-op Python cost is trimmed to the bookkeeping gossip
        needs (command map, writer index, wire cache); everything else is
        memoized per DISTINCT command dict — op pages share one dict per
        distinct (key, value) pair (OpPage.rows), so the encode/intern
        work and the key/val/payload/is_num column values are paid
        per-table-entry and gathered per-op with one vectorized take.
        That difference is what puts the paged arm of
        benches/bench_ingest.py past the single-op arm's throughput."""
        epoch = self.clock.epoch_ms
        rid = self.rid
        by_writer = self._by_writer.setdefault(rid, [])
        kcache: Dict[str, int] = {}
        vcache: Dict[str, Tuple[int, int, bool]] = {}
        # id(cmd) -> (entry idxs, kids, vids); keyed by object identity —
        # every cmd stays referenced by `cmds` for the whole loop, so ids
        # are stable.  Callers that pass per-op fresh dicts just miss.
        icache: Dict[int, Tuple[List[int], List[int], List[int]]] = {}
        # entry planes: one slot per distinct (key, value) pair
        e_key: List[int] = []
        e_val: List[int] = []
        e_pay: List[int] = []
        e_num: List[bool] = []
        # per-op planes
        c_ts: List[int] = []
        c_seq: List[int] = []
        c_eidx: List[int] = []
        commands = self._commands
        go_compat = self.go_compat_gossip
        ts_seen = self._ts_seen
        pending = self._wire_pending if self._wire is not None else None
        key_intern = self.keys.intern
        values = self.values
        seq = seq0
        for cmd, ts in zip(cmds, tss):
            ident = (ts, rid, seq)
            commands[ident] = cmd
            if go_compat:
                ts_seen.add(ts)
            by_writer.append((ident, cmd))
            ent = icache.get(id(cmd))
            if ent is None:
                eidxs: List[int] = []
                kids: List[int] = []
                vids: List[int] = []
                for k, v in cmd.items():
                    kid = kcache.get(k)
                    if kid is None:
                        kid = kcache[k] = key_intern(k)
                    enc = vcache.get(v)
                    if enc is None:
                        enc = vcache[v] = encode_value(v, values)
                    eidxs.append(len(e_key))
                    kids.append(kid)
                    vids.append(enc[1])  # payload == interned raw-string id
                    e_key.append(kid)
                    e_val.append(enc[0])
                    e_pay.append(enc[1])
                    e_num.append(enc[2])
                ent = icache[id(cmd)] = (eidxs, kids, vids)
            eidxs = ent[0]
            if len(eidxs) == 1:
                c_eidx.append(eidxs[0])
                c_ts.append(ts)
                c_seq.append(seq)
            else:  # multi-key command: one log row per pair
                for e in eidxs:
                    c_eidx.append(e)
                    c_ts.append(ts)
                    c_seq.append(seq)
            if pending is not None:
                pending.append((ts + epoch, rid, seq, ent[1], ent[2]))
            seq += 1
        self._vv[rid] = max(self._vv.get(rid, -1), seq - 1)
        if self.digest is not None and self.digest.enabled:
            self.digest.observe_rows(
                [(t, rid, seq0 + i, c) for i, (c, t) in
                 enumerate(zip(cmds, tss))],
                epoch)
        fresh = len(c_eidx)
        if not fresh:
            return None, 0
        eidx = np.asarray(c_eidx, np.intp)
        ops = {
            "ts": np.asarray(c_ts, np.int32),
            "rid": np.full(fresh, rid, np.int32),
            "seq": np.asarray(c_seq, np.int32),
            "key": np.asarray(e_key, np.int32)[eidx],
            "val": np.asarray(e_val, np.int32)[eidx],
            "payload": np.asarray(e_pay, np.int32)[eidx],
            "is_num": np.asarray(e_num, bool)[eidx],
        }
        return ops, fresh

    def _flush_wire_locked(self) -> None:
        """Drain the write-behind wire appends into the native store
        (caller holds the lock).  The batched ingest drain defers these
        per-op native calls off the admission hot path; every _wire
        reader (gossip serve, prune, rebuild) drains first."""
        if self._wire is not None and self._wire_pending:
            add_ids = self._wire.add_ids
            for ts_abs, rid, seq, kids, vids in self._wire_pending:
                add_ids(ts_abs, rid, seq, kids, vids)
        self._wire_pending.clear()

    def _merge_batch(self, ops: Dict[str, np.ndarray], fresh: int) -> None:
        """Land one packed op batch in ONE jitted merge dispatch (shared
        tail of _ingest and _ingest_local_batch; caller holds the lock)."""
        size = self._log_rows
        if size is None:
            size = int(oplog.size(self.log))
        needed = size + fresh
        while needed > self.log.capacity:
            self._grow()
        batch_cap = oplog.batch_capacity(fresh)
        # ONE device dispatch per ingest batch, however many peers' rows it
        # fuses (receive_many) — the counter the dispatch-count assertions
        # pin (crdt_merge_dispatches_total on /metrics).  The self log is
        # donated: it is rebound right below under the node lock, so XLA
        # may write the union into its buffers (TPU/GPU; plain jit on CPU).
        self.metrics.inc("merge_dispatches")
        # the op-log merge is a sorted union — record which set-union
        # engine served it (always "sort": the log's lex keys carry no
        # packed single-word form) so the union_path counter on /metrics
        # reflects EVERY set-union the node runs, not just ORSet joins
        union_engine.record_union_path("sort")
        self._count_lane_fold()
        batch = oplog.from_host_ops(batch_cap, ops)
        timing = self.recorder.enabled
        t0 = time.perf_counter() if timing else 0.0
        with devtime.dispatch_annotation("merge", enabled=timing):
            merged, n_unique = oplog.merge_checked_donating(self.log, batch)
        # int(n_unique) is a host sync: by the time the assert runs the
        # dispatch has completed, so t1 - t0 is true device+dispatch wall
        # time — the denominator of the roofline ratio (obs/devtime)
        assert int(n_unique) <= self.log.capacity
        if timing:
            devtime.observe_join(
                self.metrics.registry, str(self.rid),
                oplog.merge_checked_donating, (self.log, batch),
                time.perf_counter() - t0,
            )
        self.log = merged
        self._log_rows = int(n_unique)  # already synced by the assert
        self.metrics.inc("ops_ingested", fresh)

    def _grow(self) -> None:
        # tail-pad capacity doubling (oplog.grow is O(n) and lossless —
        # the old merge-into-bigger-empty paid a full sorted union here)
        self.log = oplog.grow(self.log, self.log.capacity * 2)
        self.metrics.inc("log_grow")

    def _count_lane_fold(self) -> None:
        # labeled per-lane merge accounting (see _metric_labels): ticks
        # once per folded lane on BOTH paths, so mesh-vs-host per-shard
        # attribution matches even though the mesh plane collapses S
        # lane folds into one device dispatch
        if self._metric_labels:
            reg = self.metrics.registry
            reg.inc("merge_dispatches", 1, **self._metric_labels)
            reg.inc("union_path", 1, path="sort", **self._metric_labels)
