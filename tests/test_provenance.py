"""Convergence flight recorder: vv-delta visibility derivation,
exactly-once propagation accounting under duplicate/reordered delivery,
device-time attribution, and the offline assembler round-trip.

The exactly-once property under test is STRUCTURAL (crdt_tpu.obs
.provenance): visibility ranges are derived from the version-vector
delta of each merge, the vv is monotone per writer, so ranges of
successive rounds are disjoint and a delivery that teaches the node
nothing (duplicate, reorder) moves no vv and emits nothing.
"""
from __future__ import annotations

import json

import pytest

from crdt_tpu.api.net import NetworkAgent, NodeHost
from crdt_tpu.api.node import ReplicaNode
from crdt_tpu.faults import FaultPlane, FaultRule, FaultyTransport, NemesisSchedule
from crdt_tpu.obs import assemble
from crdt_tpu.obs.events import SCHEMA_VERSION, EventLog, read_jsonl
from crdt_tpu.obs.provenance import (
    BirthLedger,
    FlightRecorder,
    propagation_summary,
)
from crdt_tpu.obs.registry import NULL_REGISTRY, MetricsRegistry
from crdt_tpu.utils.clock import HostClock
from crdt_tpu.utils.config import ClusterConfig
from crdt_tpu.utils.metrics import Metrics


def _steps_count(registry, origin, node) -> int:
    h = registry.histogram("op_propagation_steps",
                           origin=str(origin), node=str(node))
    return h.count if h is not None else 0


def _instrumented(rid, step):
    """ReplicaNode with a real registry + installed ledger/step clock."""
    node = ReplicaNode(rid=rid, capacity=64,
                       metrics=Metrics(registry=MetricsRegistry()))
    ledger = BirthLedger()
    node.recorder.install(ledger=ledger, step_clock=lambda: step["n"])
    return node, ledger


# ----------------------------------------------------------- birth ledger


def test_birth_ledger_basics():
    led = BirthLedger()
    assert led.birth_step(0, 0) is None and len(led) == 0
    led.note(0, 0, 5)
    led.note(0, 1, 6)
    led.note(7, 0, 9)
    assert led.birth_step(0, 0) == 5
    assert led.birth_step(0, 1) == 6
    assert led.birth_step(7, 0) == 9
    assert led.birth_step(0, 2) is None
    assert len(led) == 3
    led.note(0, 0, 8)  # overwrite keeps lookups defined
    assert led.birth_step(0, 0) == 8
    led.note(3, 4, 2)  # hole: backfilled conservatively
    assert led.birth_step(3, 0) == 2 and led.birth_step(3, 4) == 2


# ------------------------------------------- vv-delta range derivation


def test_note_visible_derives_ranges_from_vv_delta():
    reg = MetricsRegistry()
    events = EventLog(node="9")
    rec = FlightRecorder(9, reg, events=events)
    led = BirthLedger()
    for seq, step in ((0, 0), (1, 1), (2, 4)):
        led.note(1, seq, step)
    rec.install(ledger=led, step_clock=lambda: 10)
    n = rec.note_visible({1: -1}, {1: 2})
    assert n == 3
    assert _steps_count(reg, 1, 9) == 3
    [ev] = events.find(event="op_visible")
    assert (ev["origin"], ev["seq_lo"], ev["seq_hi"], ev["n"]) == (1, 0, 2, 3)
    assert ev["lag_steps"] == 10  # oldest seq: born step 0, seen step 10
    # same vv again: no progress, nothing emitted (exactly-once)
    assert rec.note_visible({1: 2}, {1: 2}) == 0
    assert _steps_count(reg, 1, 9) == 3
    # regressed vv (reordered old payload): nothing
    assert rec.note_visible({1: 2}, {1: 1}) == 0
    # next disjoint range continues where the last stopped
    led.note(1, 3, 6)
    assert rec.note_visible({1: 2}, {1: 3}) == 1
    assert _steps_count(reg, 1, 9) == 4


def test_note_visible_skips_own_and_foreign_origins():
    reg = MetricsRegistry()
    rec = FlightRecorder(2, reg)
    # own writes (origin == rid) and watermarkless Go rows (origin < 0)
    # are not propagation
    assert rec.note_visible({}, {2: 5, -1: 3}) == 0
    assert reg.histograms("op_propagation_steps") == []


# ---------------------------------- node-level exactly-once (full stack)


def test_receive_duplicate_and_reorder_emit_once():
    step = {"n": 0}
    writer, _ = _instrumented(0, step)
    puller, ledger = _instrumented(1, step)
    puller.recorder.install(ledger=writer.recorder.ledger)  # share one
    for i in range(3):
        step["n"] = i
        assert writer.add_command({f"k{i}": str(i)})
    old = writer.gossip_payload()
    step["n"] = 5
    assert writer.add_command({"k3": "3"})
    new = writer.gossip_payload()

    step["n"] = 7
    assert puller.receive(new) > 0
    reg = puller.metrics.registry
    assert _steps_count(reg, 0, 1) == 4
    # byte-identical duplicate: vv unchanged -> zero new observations
    assert puller.receive(new) == 0
    assert _steps_count(reg, 0, 1) == 4
    # older payload after newer (the PR 4 redelivery-queue shape): nothing
    assert puller.receive(old) == 0
    assert _steps_count(reg, 0, 1) == 4
    # events agree: ranges are disjoint and cover each seq exactly once
    seen = []
    for ev in puller.events.find(event="op_visible"):
        seen.extend(range(ev["seq_lo"], ev["seq_hi"] + 1))
    assert sorted(seen) == [0, 1, 2, 3]


def test_receive_many_fused_counts_overlaps_once():
    step = {"n": 0}
    writer, ledger = _instrumented(0, step)
    puller, _ = _instrumented(1, step)
    puller.recorder.install(ledger=writer.recorder.ledger)
    assert writer.add_command({"a": "1"})
    p1 = writer.gossip_payload()
    assert writer.add_command({"b": "2"})
    p2 = writer.gossip_payload()  # superset of p1
    step["n"] = 3
    # one fused round carrying overlapping payloads: ONE vv delta, so the
    # shared seq is visible exactly once
    assert puller.receive_many([p1, p2]) == 2
    assert _steps_count(puller.metrics.registry, 0, 1) == 2


def test_redelivery_queue_duplicate_exactly_once():
    """Same property through the PR 4 fault plane: a 'duplicate' wire
    fault queues a byte-identical redelivery; the second delivery must
    observe nothing."""
    host = NodeHost(rid=1, peers=[], port=0)
    host.node.add_command({"x": "1"}, ts=10)
    host.node.add_command({"y": "2"}, ts=11)
    host.start_server()
    try:
        plane = FaultPlane(NemesisSchedule(
            seed=0, steps=1000, nodes=2,
            rules=(FaultRule("duplicate"),), skews=(),
        ))
        node = ReplicaNode(rid=0, capacity=64,
                           metrics=Metrics(registry=MetricsRegistry()))
        agent = NetworkAgent(node, [], ClusterConfig())
        t = FaultyTransport(host.url, plane, "0", "1")
        assert agent.pull_from(t)  # delivered AND queued for redelivery
        assert t.pending_redelivery() == 1
        assert _steps_count(node.metrics.registry, 1, 0) == 0  # no ledger
        h = node.metrics.registry.histogram("op_propagation",
                                            origin="1", node="0")
        assert h is not None and h.count == 2
        assert not agent.pull_from(t)  # the queued duplicate lands
        h2 = node.metrics.registry.histogram("op_propagation",
                                             origin="1", node="0")
        assert h2.count == 2  # exactly once per (origin, seq, observer)
    finally:
        host.stop_server()


def test_propagation_seconds_across_epochs():
    """The seconds histogram derives from the op's absolute WIRE ts, so
    it survives different host-clock epochs (cross-process shape)."""
    writer = ReplicaNode(rid=0, capacity=64, clock=HostClock(),
                         metrics=Metrics(registry=MetricsRegistry()))
    puller = ReplicaNode(rid=1, capacity=64, clock=HostClock(),
                         metrics=Metrics(registry=MetricsRegistry()))
    assert writer.clock.epoch_ms != 0 or True  # epochs are independent
    writer.add_command({"a": "1"})
    assert puller.receive(writer.gossip_payload()) > 0
    h = puller.metrics.registry.histogram("op_propagation",
                                          origin="0", node="1")
    assert h is not None and h.count == 1
    assert h.sum >= 0.0  # clamped: skew can't go negative


def test_propagation_summary_rolls_up_edges():
    step = {"n": 0}
    writer, _ = _instrumented(0, step)
    puller, _ = _instrumented(1, step)
    puller.recorder.install(ledger=writer.recorder.ledger)
    writer.add_command({"a": "1"})
    step["n"] = 2
    puller.receive(writer.gossip_payload())
    out = propagation_summary(writer.metrics.registry,
                              puller.metrics.registry)
    assert out["propagation_steps_count"] == 1
    assert out["propagation_s_count"] == 1
    assert out["propagation_steps_p50"] >= 2.0  # lag 2 -> bucket bound


def test_recorder_disabled_with_null_registry():
    node = ReplicaNode(rid=0, capacity=64,
                       metrics=Metrics(registry=NULL_REGISTRY))
    assert not node.recorder.enabled
    node.add_command({"a": "1"})
    assert node.events.find(event="op_birth") == []


# ------------------------------------------------- device-time attribution


def test_dispatch_annotation_carries_trace_id():
    from crdt_tpu.obs import devtime
    from crdt_tpu.obs.trace import span

    with span("crdt.pull") as tid:
        with devtime.dispatch_annotation("merge") as label:
            assert label == f"crdt.join.merge#trace={tid}"


# ----------------------------------------------------- events satellites


def test_event_ring_eviction_is_counted():
    reg = MetricsRegistry()
    log = EventLog(node="3", capacity=4, registry=reg)
    for i in range(6):
        log.emit("tick", i=i)
    assert log.dropped == 2
    assert reg.counter_value("events_dropped", node="3") == 2
    assert len(log) == 4
    assert log.tail(1)[0]["i"] == 5  # newest survives, oldest evicted


def test_schema_version_and_step_stamped(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    log = EventLog(node="1", path=path, step_clock=lambda: 41)
    rec = log.emit("boot", port=1)
    assert rec["v"] == SCHEMA_VERSION == 2
    assert rec["step"] == 41
    log.close()
    [line] = read_jsonl(path)
    assert line["v"] == 2 and line["step"] == 41 and line["event"] == "boot"


def test_ring_dropped_gauge_in_health_sample():
    from crdt_tpu.obs import health

    node = ReplicaNode(rid=0, capacity=64,
                       metrics=Metrics(registry=MetricsRegistry()),
                       events=EventLog(node="0", capacity=2))
    for i in range(5):
        node.events.emit("tick", i=i)
    health.sample_kv_node(node.metrics.registry, node)
    assert node.metrics.registry.gauge_value(
        "events_ring_dropped", node="0") == 3


# ------------------------------------------------------------- assembler


def _write_jsonl(path, records):
    with open(path, "w", encoding="utf-8") as fh:
        for r in records:
            fh.write(json.dumps(r, sort_keys=True) + "\n")
    return str(path)


def _synthetic_logs(tmp_path, with_fault_window=False):
    """Two node logs for one gossip round (node 1 serves, node 0 pulls)
    plus births/visibilities; optionally a second, laggy visibility."""
    t = 1_000_000
    n1 = [
        {"v": 2, "ts_ms": t + 1, "node": "1", "event": "boot", "step": 0},
        {"v": 2, "ts_ms": t + 2, "node": "1", "event": "op_birth",
         "origin": 1, "seq": 0, "op_ts_ms": t + 2, "step": 0},
        {"v": 2, "ts_ms": t + 10, "node": "1", "event": "gossip_serve",
         "trace": "tr-1", "ops": 1, "step": 2},
    ]
    n0 = [
        {"v": 2, "ts_ms": t + 0, "node": "0", "event": "boot", "step": 0},
        {"v": 2, "ts_ms": t + 12, "node": "0", "event": "pull_merge",
         "trace": "tr-1", "fresh": 1, "step": 2},
        {"v": 2, "ts_ms": t + 12, "node": "0", "event": "op_visible",
         "trace": "tr-1", "origin": 1, "seq_lo": 0, "seq_hi": 0, "n": 1,
         "lag_steps": 2, "step": 2},
    ]
    if with_fault_window:
        # enough low-lag visibilities that the median stays at 2 and the
        # spike threshold sits at the floor (12) — then one 60-step lag
        for k in (1, 2):
            n1.append({"v": 2, "ts_ms": t + 13 + k, "node": "1",
                       "event": "op_birth", "origin": 1, "seq": k,
                       "op_ts_ms": t + 13 + k, "step": 2 + k})
            n0.append({"v": 2, "ts_ms": t + 16 + k, "node": "0",
                       "event": "op_visible", "origin": 1, "seq_lo": k,
                       "seq_hi": k, "n": 1, "lag_steps": 2, "step": 4 + k})
        n1.append({"v": 2, "ts_ms": t + 20, "node": "1",
                   "event": "op_birth", "origin": 1, "seq": 3,
                   "op_ts_ms": t + 20, "step": 5})
        n0.append({"v": 2, "ts_ms": t + 90, "node": "0",
                   "event": "op_visible", "origin": 1, "seq_lo": 3,
                   "seq_hi": 3, "n": 1, "lag_steps": 60, "step": 65})
    return (_write_jsonl(tmp_path / "node0.jsonl", n0),
            _write_jsonl(tmp_path / "node1.jsonl", n1))


def test_assembler_round_trip_two_nodes(tmp_path):
    p0, p1 = _synthetic_logs(tmp_path)
    records = assemble.load_node_logs([p0, p1])
    assert [r["node"] for r in records][0] == "0"  # ts-sorted
    trace = assemble.assemble_trace(records)
    evs = trace["traceEvents"]
    names = {e.get("args", {}).get("name") for e in evs if e["ph"] == "M"}
    assert {"node slot 0", "node slot 1",
            "nemesis (applied faults)"} <= names
    [x] = [e for e in evs if e["ph"] == "X"]
    assert x["name"] == "pull_merge" and x["args"]["trace"] == "tr-1"
    assert x["dur"] >= 1
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}  # serve->merge link
    assert len({e["id"] for e in flows}) == 1
    # boots (never part of a span) appear as instants on their own track
    assert any(e["ph"] == "i" and e["name"] == "boot" for e in evs)
    blame = assemble.blame_report(records)
    assert blame["n_visible"] == 1
    assert blame["n_spikes"] == 0 and blame["coverage"] == 1.0


def test_blame_attributes_spike_to_fault_window(tmp_path):
    p0, p1 = _synthetic_logs(tmp_path, with_fault_window=True)
    records = assemble.load_node_logs([p0, p1])
    # without any fault evidence the spike must be flagged, not dropped
    blame = assemble.blame_report(records)
    assert blame["n_spikes"] == 1
    assert blame["spikes"][0]["cause"] == "unexplained"
    assert blame["coverage"] == 0.0
    # a partition window (drop records) covering birth->visible explains it
    faults = [{"step": 10, "fault": "drop", "src": "1", "dst": "0",
               "op": "gossip"}]
    blame = assemble.blame_report(records, faults)
    assert blame["n_spikes"] == 1 and blame["coverage"] == 1.0
    assert blame["spikes"][0]["cause"]["kind"] == "drop"
    # a fault on an UNINVOLVED edge does not explain this spike
    blame = assemble.blame_report(
        records, [{"step": 10, "fault": "drop", "src": "2", "dst": "3"}])
    assert blame["spikes"][0]["cause"] == "unexplained"


def test_assemble_cli_and_postmortem(tmp_path):
    p0, p1 = _synthetic_logs(tmp_path, with_fault_window=True)
    faults = _write_jsonl(
        tmp_path / "faults.jsonl",
        [{"step": 10, "fault": "drop", "src": "1", "dst": "0"}],
    )
    out = tmp_path / "trace.json"
    blame_out = tmp_path / "blame.json"
    rc = assemble.main([p0, p1, "--fault-log", faults, "--out", str(out),
                        "--blame", str(blame_out),
                        "--min-coverage", "0.95"])
    assert rc == 0
    trace = json.loads(out.read_text())
    assert trace["traceEvents"] and trace["displayTimeUnit"] == "ms"
    # the nemesis track carries the fault instant, placed via step anchors
    assert any(e["tid"] == 0 and e["ph"] == "i"
               for e in trace["traceEvents"])
    assert json.loads(blame_out.read_text())["coverage"] == 1.0
    # unexplained spike -> coverage gate fails loudly
    rc = assemble.main([p0, p1, "--out", str(out),
                        "--min-coverage", "0.95"])
    assert rc == 1
    # postmortem bundle carries logs + faults + trace + blame
    import tarfile

    bundle = assemble.write_postmortem(
        str(tmp_path / "pm" / "postmortem-0.tar.gz"), [p0, p1],
        fault_records=[{"step": 10, "fault": "drop"}])
    with tarfile.open(bundle) as tf:
        names = set(tf.getnames())
    assert {"node0.jsonl", "node1.jsonl", "faults.jsonl",
            "trace.json", "blame.json"} <= names


def test_obs_main_dispatches_assemble(tmp_path, capsys):
    from crdt_tpu.obs.__main__ import main as obs_main

    p0, p1 = _synthetic_logs(tmp_path)
    out = tmp_path / "t.json"
    assert obs_main(["assemble", p0, p1, "--out", str(out)]) == 0
    assert json.loads(out.read_text())["traceEvents"]
    assert obs_main(["no-such-cmd"]) == 2


# ---- keyspace tier: tenant/shard-labeled propagation (ISSUE 16) ----


def test_keyspace_tenant_labeled_exactly_once_dup_reorder():
    """Tenant writes through the front door propagate to a peer keyspace
    with {tenant, shard}-labeled exactly-once derivation: duplicate and
    stale-reordered payload deliveries add ZERO observations, and the
    op_visible events carry the shard label plus the per-tenant count."""
    from crdt_tpu.keyspace import KeyspaceFrontDoor, ShardedKeyspace

    step = {"n": 0}
    writer = ShardedKeyspace(rid=0, n_shards=2, capacity=64,
                             metrics=Metrics(registry=MetricsRegistry()))
    puller = ShardedKeyspace(rid=1, n_shards=2, capacity=64,
                             metrics=Metrics(registry=MetricsRegistry()))
    # per-shard FLEET-WIDE ledgers: shard i shares one (rid, seq) space
    # on every member, disjoint from its siblings'
    ledgers = [BirthLedger(), BirthLedger()]
    for ks in (writer, puller):
        for i, shard in enumerate(ks.shards):
            shard.recorder.install(ledger=ledgers[i],
                                   step_clock=lambda: step["n"])
    door = KeyspaceFrontDoor(writer, max_batch=1)
    for i in range(3):
        step["n"] = i
        assert door.admit_kv("t-acme", f"k{i}", str(i), timeout=5.0)
    old = [writer.gossip_payload(s, None) for s in range(2)]
    step["n"] = 4
    assert door.admit_kv("t-acme", "k3", "3", timeout=5.0)
    assert door.admit_kv("t-bolt", "kb", "vb", timeout=5.0)
    new = [writer.gossip_payload(s, None) for s in range(2)]

    def tenant_counts():
        out = {}
        reg = puller.shards[0].metrics.registry  # shared across shards
        for labels, h in reg.histograms("op_propagation_steps"):
            t = labels.get("tenant")
            if t:
                assert labels["shard"] in ("0", "1")
                assert labels["origin"] == "0" and labels["node"] == "1"
                out[t] = out.get(t, 0) + h.count
        return out

    step["n"] = 6
    assert sum(puller.receive(s, new[s]) for s in range(2)) == 5
    assert tenant_counts() == {"t-acme": 4, "t-bolt": 1}
    # byte-identical duplicates: vv unchanged -> zero new observations
    assert sum(puller.receive(s, new[s]) for s in range(2)) == 0
    # stale payloads after newer ones (reorder): still zero
    assert sum(puller.receive(s, old[s]) for s in range(2)) == 0
    assert tenant_counts() == {"t-acme": 4, "t-bolt": 1}
    # events agree: shard-labeled, each seq exactly once per shard, and
    # the tenants rollup matches the histogram counts
    seen = {}
    tenants = {}
    for shard in puller.shards:  # each shard keeps its own black box here
        for ev in shard.events.find(event="op_visible"):
            key = (ev["shard"], ev["origin"])
            seen.setdefault(key, []).extend(
                range(ev["seq_lo"], ev["seq_hi"] + 1))
            for t, n in (ev.get("tenants") or {}).items():
                tenants[t] = tenants.get(t, 0) + n
    for key, seqs in seen.items():
        assert sorted(seqs) == sorted(set(seqs)), key
    assert tenants == {"t-acme": 4, "t-bolt": 1}


def test_assembler_lease_track_round_trip(tmp_path):
    """Lease events assemble into the per-slot track: fence epochs as
    counter samples, lease instants on the slot track, and a handoff
    (grant by a DIFFERENT node) drawn as a flow arrow between the two
    holders' node tracks."""
    t = 1_000_000
    n0 = [
        {"v": 2, "ts_ms": t, "node": "0", "event": "boot", "step": 0},
        {"v": 2, "ts_ms": t + 5, "node": "0", "event": "lease_grant",
         "slot": 0, "fence": 1, "holder": "http://a", "trace": "tr-l1",
         "step": 1},
        {"v": 2, "ts_ms": t + 8, "node": "0", "event": "lease_renew",
         "slot": 0, "fence": 1, "holder": "http://a", "step": 2},
        {"v": 2, "ts_ms": t + 20, "node": "0", "event": "lease_expire",
         "slot": 0, "fence": 1, "step": 4},
    ]
    n1 = [
        {"v": 2, "ts_ms": t + 1, "node": "1", "event": "boot", "step": 0},
        {"v": 2, "ts_ms": t + 30, "node": "1", "event": "lease_grant",
         "slot": 0, "fence": 2, "holder": "http://b", "trace": "tr-l2",
         "step": 6},
        {"v": 2, "ts_ms": t + 35, "node": "1", "event":
         "cas_fenced_reject", "slot": 0, "fence": 1, "known": 2,
         "trace": "tr-z", "step": 7},
    ]
    p0 = _write_jsonl(tmp_path / "node0.jsonl", n0)
    p1 = _write_jsonl(tmp_path / "node1.jsonl", n1)
    records = assemble.load_node_logs([p0, p1])
    trace = assemble.assemble_trace(records)
    evs = trace["traceEvents"]
    # the slot track exists and is named
    meta = {e.get("args", {}).get("name") for e in evs if e["ph"] == "M"}
    assert "lease slot 0" in meta
    # fence epochs render as counter samples, monotone 1 -> 2
    fences = [e["args"]["fence"] for e in evs
              if e["ph"] == "C" and e["name"] == "lease fence s0"]
    assert fences == sorted(fences) and fences[-1] == 2
    # every lease event is an instant on the slot track
    kinds = [e["name"] for e in evs
             if e["ph"] == "i" and e.get("args", {}).get("slot") == 0]
    assert {"lease_grant", "lease_renew", "lease_expire",
            "cas_fenced_reject"} <= set(kinds)
    # the handoff (node 0's lease -> node 1's grant) is a flow arrow
    flows = [e for e in evs if e["ph"] in ("s", "f")
             and e["name"] == "lease_handoff"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert len({e["id"] for e in flows}) == 1
