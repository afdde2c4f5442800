"""Device-mesh sharded anti-entropy tests (parallel.meshplane).

The mesh plane's whole claim is "same bits, fewer dispatches": ONE
compiled step folds every keyspace shard lane, and each lane's merged
log / vv / state is bit-identical to what S independent host dispatches
would have produced.  These tests pin both halves:

* randomized multi-tenant traces driven through a mesh keyspace and a
  host-path twin, compared per shard down to the raw OpLog columns —
  for every engine (the auto-selected one and single-device vmap
  fusion);
* exactly ONE label-free `merge_dispatches` tick per converge (vs S on
  the host path), with per-shard attribution surviving as
  `merge_dispatches{shard=i}` labels — asserted on a rendered AND a
  served (real socket) /metrics scrape;
* corrupt-shard isolation: a payload that fails structural validation
  quarantines ITS lane while the siblings still fold in the same step;
* engine failure lands every lane via its own inline host dispatch
  (commit_inline) — bits still right — and is LOUD: `meshplane_fallbacks`
  ticks, a `meshplane_fallback` event carries the error text, and an
  `EngineFallback` warning is raised.

conftest.py pins JAX_PLATFORMS=cpu with 8 emulated host devices, so
the pjit engine gets a real multi-device mesh under CI.
"""
from __future__ import annotations

import json
import random
import re
import warnings

import jax
import numpy as np
import pytest

from crdt_tpu.api.node import ReplicaNode
from crdt_tpu.keyspace import ShardedKeyspace, qualify
from crdt_tpu.models import oplog
from crdt_tpu.models.oplog_engine import EngineFallback
from crdt_tpu.parallel.meshplane import (MESH_MODES, MeshPlane,
                                         _mesh_divisor, select_engine)
from crdt_tpu.utils.clock import ManualClock
from crdt_tpu.utils.config import ClusterConfig
from crdt_tpu.utils.metrics import Metrics

N_SHARDS = 4
TENANTS = ("t-acme", "t-bravo", "t-noisy")
_COLS = ("ts", "rid", "seq", "key", "val", "payload", "is_num")


def _twin_keyspaces(n_shards: int = N_SHARDS, engine=None):
    """A mesh keyspace + a host-path twin sharing ONE ManualClock (same
    epoch => same rebased ts => bit-comparable logs).  ``engine`` pins a
    specific mesh engine via the MeshPlane override."""
    clock = ManualClock()
    host = ShardedKeyspace(rid=0, n_shards=n_shards, capacity=64,
                           metrics=Metrics(), clock=clock, mesh="off")
    mesh = ShardedKeyspace(rid=0, n_shards=n_shards, capacity=64,
                           metrics=Metrics(), clock=clock, mesh="on")
    if engine is not None:
        mesh._meshplane = MeshPlane(
            n_shards, mode="on", metrics=mesh.shards[0].metrics,
            engine=engine)
    return host, mesh, clock


def _writers(ks: ShardedKeyspace, clock, rids=(100, 101)):
    """Per-(shard, rid) writer nodes on the SAME clock — the gossip
    sources whose payloads both twins fold."""
    return {(s, r): ReplicaNode(rid=r, capacity=64, clock=clock)
            for s in range(ks.n_shards) for r in rids}


def _random_round(rng, ks, writers, clock, n_ops=8):
    """One gossip round: random tenant-qualified writes land on the
    writer owning their shard; returns one payload per shard (None for
    shards nothing routed to this round)."""
    rids = sorted({r for (_, r) in writers})
    for _ in range(n_ops):
        tenant = rng.choice(TENANTS)
        key = f"k{rng.randrange(12)}"
        val = f"v{rng.randrange(1000)}"
        shard = ks.shard_of(tenant, key)
        writers[(shard, rng.choice(rids))].add_commands(
            [{qualify(tenant, key): val}])
        clock.advance(rng.randrange(1, 3))
    payloads = []
    for s in range(ks.n_shards):
        merged = {}
        for r in rids:
            p = writers[(s, r)].gossip_payload()
            if p:
                merged.update(p)
        payloads.append(merged or None)
    return payloads


def _assert_shards_bit_equal(host: ShardedKeyspace, mesh: ShardedKeyspace):
    """state + vv + the live prefix of every raw OpLog column, per shard."""
    for i, (h, m) in enumerate(zip(host.shards, mesh.shards)):
        assert m.get_state() == h.get_state(), f"shard {i} state diverged"
        assert m.version_vector() == h.version_vector(), \
            f"shard {i} vv diverged"
        n_h, n_m = int(oplog.size(h.log)), int(oplog.size(m.log))
        assert n_m == n_h, f"shard {i} live rows {n_m} != {n_h}"
        for col in _COLS:
            a = np.asarray(getattr(h.log, col))[:n_h]
            b = np.asarray(getattr(m.log, col))[:n_h]
            assert np.array_equal(a, b), \
                f"shard {i} column {col} not bit-identical"


# ---- engine selection ----

def test_mesh_divisor():
    assert _mesh_divisor(4, 8) == 4
    assert _mesh_divisor(6, 4) == 3
    assert _mesh_divisor(5, 4) == 1
    assert _mesh_divisor(8, 8) == 8


def test_select_engine_modes():
    with pytest.raises(ValueError):
        select_engine(4, "bogus")
    assert select_engine(4, "off") is None
    assert select_engine(0, "on") is None
    # auto refuses to fuse a single lane — nothing to amortize
    assert select_engine(1, "auto") is None
    # "on" always fuses; with the conftest's 8 emulated devices and a
    # lane count they divide, a sharded engine (pjit preferred, else the
    # shard_map compat shim) must be picked over plain vmap
    eng = select_engine(4, "on")
    assert eng in ("pjit", "shard_map", "vmap")
    if len(jax.devices()) >= 2:
        assert eng in ("pjit", "shard_map")
    # a prime lane count can't split across the mesh: vmap fusion
    assert select_engine(7, "on") == "vmap" or len(jax.devices()) >= 7


def test_config_knob_validated():
    assert ClusterConfig(keyspace_mesh="on").keyspace_mesh == "on"
    with pytest.raises(ValueError):
        ClusterConfig(keyspace_mesh="bogus")
    for mode in MESH_MODES:
        ClusterConfig(keyspace_mesh=mode)


# ---- bit-parity: mesh vs host twin, every engine ----

@pytest.mark.parametrize("engine", [None, "vmap"])
def test_mesh_parity_randomized_multitenant(engine):
    """Randomized multi-tenant trace: after every fused converge, each
    mesh shard is bit-identical (state, vv, all 7 raw OpLog columns) to
    its host-path twin.  ``None`` runs whatever select_engine picks in
    this environment; shard_map exercises the compat-shim fallback and
    vmap the single-device fusion."""
    host, mesh, clock = _twin_keyspaces(engine=engine)
    assert mesh.mesh_active
    if engine is not None:
        assert mesh.mesh_engine == engine
    writers = _writers(mesh, clock)
    rng = random.Random(1234)
    for step in range(6):
        payloads = _random_round(rng, mesh, writers, clock)
        for i, p in enumerate(payloads):
            if p is not None:
                host.receive(i, p)
        results = mesh.receive_all(payloads)
        assert all(isinstance(r, int) for r in results)
        _assert_shards_bit_equal(host, mesh)
    assert mesh.state() == host.state()
    assert mesh.state()  # the trace actually wrote something


# ---- one dispatch per step + per-shard attribution ----

def test_one_dispatch_per_step_and_shard_labels():
    """The perf pin: a fused converge costs ONE label-free
    merge_dispatches tick regardless of S, where the host twin pays one
    per shard — while the per-shard labeled counters tick identically
    on both paths."""
    host, mesh, clock = _twin_keyspaces()
    writers = _writers(mesh, clock)
    rng = random.Random(7)
    payloads = _random_round(rng, mesh, writers, clock, n_ops=16)
    n_nonempty = sum(1 for p in payloads if p is not None)
    assert n_nonempty == N_SHARDS  # 16 ops over 4 shards: all hit

    before_m = mesh.shards[0].metrics._counts.get("merge_dispatches", 0)
    before_h = host.shards[0].metrics._counts.get("merge_dispatches", 0)
    mesh.receive_all(payloads)
    for i, p in enumerate(payloads):
        if p is not None:
            host.receive(i, p)
    mesh_ticks = (mesh.shards[0].metrics._counts["merge_dispatches"]
                  - before_m)
    host_ticks = (host.shards[0].metrics._counts["merge_dispatches"]
                  - before_h)
    assert mesh_ticks == 1, "mesh step must be ONE device dispatch"
    assert host_ticks == n_nonempty, "host path pays one per shard"

    # per-shard attribution is path-independent: every folded lane ticks
    # merge_dispatches{shard=i} and union_path{path=sort,shard=i} once,
    # on the rendered scrape of BOTH twins
    for ks in (mesh, host):
        text = ks.shards[0].metrics.registry.render_prometheus()
        for i in range(N_SHARDS):
            assert f'crdt_merge_dispatches_total{{shard="{i}"}} 1' in text
            assert (f'crdt_union_path_total{{path="sort",shard="{i}"}} 1'
                    in text)


def test_zero_fresh_converge_skips_device():
    """Idempotent redelivery: a round where every lane folds nothing
    commits inline — no device dispatch at all."""
    host, mesh, clock = _twin_keyspaces()
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(3), mesh, writers, clock)
    mesh.receive_all(payloads)
    before = mesh.shards[0].metrics._counts["merge_dispatches"]
    results = mesh.receive_all(payloads)  # pure redelivery
    assert all(r == 0 for r in results)
    assert mesh.shards[0].metrics._counts["merge_dispatches"] == before
    assert all(isinstance(r, int) for r in
               mesh.receive_all([None] * N_SHARDS))
    assert mesh.shards[0].metrics._counts["merge_dispatches"] == before


# ---- corrupt-shard isolation inside the fused step ----

def test_corrupt_shard_isolated_siblings_fold():
    """A payload that fails structural validation quarantines its OWN
    lane (error-string result, shard state untouched) while the
    siblings still converge — in the same single dispatch."""
    host, mesh, clock = _twin_keyspaces()
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(11), mesh, writers, clock,
                             n_ops=16)
    corrupt_shard = 1
    payloads[corrupt_shard] = {"nemesis:corrupt:key": {"a": "b"}}
    for i, p in enumerate(payloads):
        if i != corrupt_shard and p is not None:
            host.receive(i, p)

    before = mesh.shards[0].metrics._counts.get("merge_dispatches", 0)
    results = mesh.receive_all(payloads, quarantine=True)
    assert isinstance(results[corrupt_shard], str)
    assert "ValueError" in results[corrupt_shard]
    for i, r in enumerate(results):
        if i != corrupt_shard:
            assert isinstance(r, int) and r > 0, f"sibling {i} didn't fold"
    # the corrupt lane rode along empty: its shard matches the host twin
    # (which never saw the corrupt payload), and the siblings match too
    _assert_shards_bit_equal(host, mesh)
    assert (mesh.shards[0].metrics._counts["merge_dispatches"]
            - before) == 1

    # without quarantine the same payload raises — after every lane's
    # lock was released (a second receive_all must not deadlock)
    with pytest.raises(ValueError):
        mesh.receive_all(payloads, quarantine=False)
    mesh.receive_all([None] * N_SHARDS)


# ---- engine failure: inline host fallback ----

def test_step_failure_falls_back_to_inline_commits():
    """If the compiled step blows up, every lane lands via its own
    inline host dispatch: bits identical to the host path, locks
    released, meshplane_fallbacks ticked."""
    host, mesh, clock = _twin_keyspaces()
    plane = mesh._plane()

    def boom(capacity, batch_cap):
        raise RuntimeError("injected engine failure")

    plane._step_for = boom
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(5), mesh, writers, clock)
    for i, p in enumerate(payloads):
        if p is not None:
            host.receive(i, p)
    with pytest.warns(EngineFallback, match="injected engine failure"):
        results = mesh.receive_all(payloads)
    assert all(isinstance(r, int) for r in results)
    _assert_shards_bit_equal(host, mesh)
    counts = mesh.shards[0].metrics._counts
    assert counts["meshplane_fallbacks"] == 1
    # fallback pays the per-lane dispatches (the host path's cost)
    assert counts["merge_dispatches"] == sum(
        1 for p in payloads if p is not None)


def test_step_failure_is_loud_and_keeps_lanes_consistent():
    """An injected fused-step failure must surface — the warning (an
    error under simplefilter, as chip_smoke.py runs), the event with the
    error text, the counter — and still leave every lane's host indexes
    exactly level with its device log: no lane's vv runs ahead."""
    _, mesh, clock = _twin_keyspaces()
    plane = mesh._plane()

    def boom(capacity, batch_cap):
        raise RuntimeError("injected chip failure")

    plane._step_for = boom
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(9), mesh, writers, clock)
    with warnings.catch_warnings():
        warnings.simplefilter("error", EngineFallback)
        with pytest.raises(EngineFallback, match="injected chip failure"):
            mesh.receive_all(payloads)
    assert mesh.shards[0].metrics._counts["meshplane_fallbacks"] == 1
    events = mesh.shards[0].events.find(event="meshplane_fallback")
    assert len(events) == 1
    assert "RuntimeError: injected chip failure" in events[0]["error"]
    for i, shard in enumerate(mesh.shards):
        n_writers = shard._n_writers()
        dev_vv = np.asarray(oplog.version_vector(shard.log, n_writers))
        host_vv = shard.version_vector()
        assert {r: int(v) for r, v in enumerate(dev_vv) if v >= 0} \
            == host_vv, f"shard {i}: host indexes ahead of the log"
        assert shard._log_rows == int(oplog.size(shard.log))
    _assert_no_lock_leak(mesh)


def test_lane_count_mismatch_aborts_cleanly():
    host, mesh, clock = _twin_keyspaces()
    with pytest.raises(ValueError):
        mesh.receive_all([None] * (N_SHARDS + 1))
    plane = mesh._plane()
    pendings = [s.merge_begin([]) for s in mesh.shards[:2]]
    with pytest.raises(ValueError):
        plane.converge(pendings)
    # locks were released by the abort: lanes still usable
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(2), mesh, writers, clock)
    assert sum(r for r in mesh.receive_all(payloads)
               if isinstance(r, int)) > 0


# ---- failure paths must never leak a lane's node lock ----

def _assert_no_lock_leak(ks: ShardedKeyspace):
    """Every shard's node lock is free (non-blocking probe — a leaked
    lock fails the assert instead of hanging the test run)."""
    for i, shard in enumerate(ks.shards):
        assert shard._lock.acquire(blocking=False), f"shard {i} lock leaked"
        shard._lock.release()


def test_adoption_failure_quarantines_lane_without_lock_leak():
    """A payload that PASSES structural validation but fails at ADOPTION
    time inside merge_begin (non-trivial frontier with no __summary__ —
    receiver-state dependent, so validate_payload can't pre-screen it)
    must not leak the earlier lanes' node locks: with quarantine it
    becomes that lane's error-string result while every sibling still
    folds; without quarantine it raises only after every already-held
    lane landed inline."""
    host, mesh, clock = _twin_keyspaces()
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(23), mesh, writers, clock,
                             n_ops=16)
    bad_shard = 2
    bad = {"__frontier__": {"7": 5}}  # truncated: frontier, no summary
    assert mesh.shards[bad_shard].validate_payload(bad) is None
    payloads[bad_shard] = bad
    for i, p in enumerate(payloads):
        if i != bad_shard and p is not None:
            host.receive(i, p)

    results = mesh.receive_all(payloads, quarantine=True)
    assert isinstance(results[bad_shard], str)
    assert "__summary__" in results[bad_shard]
    for i, r in enumerate(results):
        if i != bad_shard and payloads[i] is not None:
            assert isinstance(r, int) and r > 0, f"sibling {i} didn't fold"
    _assert_no_lock_leak(mesh)
    # the quarantined lane rode along empty: bit-equal to the host twin
    # (which never saw the bad payload)
    _assert_shards_bit_equal(host, mesh)

    # without quarantine the adoption failure propagates — but the lanes
    # begun before it landed inline and released their locks first
    payloads2 = _random_round(random.Random(24), mesh, writers, clock)
    payloads2[bad_shard] = dict(bad)
    with pytest.raises(ValueError, match="__summary__"):
        mesh.receive_all(payloads2, quarantine=False)
    _assert_no_lock_leak(mesh)
    assert all(isinstance(r, int)
               for r in mesh.receive_all([None] * N_SHARDS))


def test_commit_failure_still_commits_sibling_lanes():
    """If ONE lane's post-dispatch commit raises (accounting failure),
    converge still commits every sibling's fused output before
    re-raising — no sibling is left with its node lock held and its
    host indexes ahead of its log."""
    host, mesh, clock = _twin_keyspaces()
    writers = _writers(mesh, clock)
    payloads = _random_round(random.Random(31), mesh, writers, clock,
                             n_ops=16)
    for i, p in enumerate(payloads):
        if p is not None:
            host.receive(i, p)
    bad = next(i for i, p in enumerate(payloads) if p is not None)

    def boom():
        raise RuntimeError("injected commit failure")

    mesh.shards[bad]._count_lane_fold = boom
    try:
        with pytest.raises(RuntimeError, match="injected commit failure"):
            mesh.receive_all(payloads)
    finally:
        del mesh.shards[bad]._count_lane_fold  # restore the class method
    _assert_no_lock_leak(mesh)
    # every lane's fused output committed (the failing lane's log was
    # rebound before its accounting blew up), so the twin still matches
    _assert_shards_bit_equal(host, mesh)
    # and the keyspace folds normally on the next round
    assert all(isinstance(r, int)
               for r in mesh.receive_all([None] * N_SHARDS))


def test_fused_flush_converge_failure_fails_claims_and_releases_lanes():
    """flush_all_fused: a converge that re-raises (one lane's commit
    failed) must fail every outstanding drain claim — waiting tickets
    observe the error instead of hanging — and release every drain slot
    and node lock, leaving the door fully usable."""
    from crdt_tpu.keyspace import KeyspaceFrontDoor

    clock = ManualClock()
    mesh = ShardedKeyspace(rid=0, n_shards=N_SHARDS, capacity=64,
                           metrics=Metrics(), clock=clock, mesh="on")
    door = KeyspaceFrontDoor(mesh, max_batch=1024)
    groups = {}
    for i in range(16):
        key = f"k{i}"
        shard = mesh.shard_of("t-acme", key)
        groups.setdefault(shard, []).append(
            (None, {qualify("t-acme", key): f"v{i}"}, "t-acme"))
    lane_tickets = door._submit_groups(groups, "t-acme")
    bad = next(iter(groups))

    def boom():
        raise RuntimeError("injected commit failure")

    mesh.shards[bad]._count_lane_fold = boom
    try:
        with pytest.raises(RuntimeError, match="injected commit failure"):
            door.flush_all()
    finally:
        del mesh.shards[bad]._count_lane_fold
    for _, ticket in lane_tickets:
        assert ticket.done, "a drained ticket was left unresolved"
        with pytest.raises(RuntimeError, match="injected commit failure"):
            ticket.wait(0)
    _assert_no_lock_leak(mesh)
    for lane in door.lanes:
        assert lane._drain_lock.acquire(blocking=False), \
            f"lane {lane.name} drain slot leaked"
        lane._drain_lock.release()
    # the door keeps admitting and draining after the failed fused flush
    assert door.admit_kv("t-acme", "fresh-key", "fresh-val",
                         timeout=5.0) is not None
    assert mesh.get("t-acme", "fresh-key") == "fresh-val"


# ---- served /metrics scrape over a real socket ----

def test_served_scrape_shows_per_shard_counters():
    """End-to-end: a mesh-path ks_pull over real sockets, then the
    puller's served GET /metrics carries the per-shard labeled
    merge_dispatches/union_path counters next to the ONE label-free
    fused-dispatch tick."""
    import threading
    import urllib.request

    from crdt_tpu.api.net import NodeHost, RemotePeer
    from crdt_tpu.keyspace import TENANT_HEADER

    cfg = ClusterConfig(keyspace_shards=N_SHARDS, keyspace_capacity=64,
                        keyspace_mesh="on")
    a = NodeHost(rid=0, peers=[], config=cfg)
    b = NodeHost(rid=1, peers=[], config=cfg)
    assert b.keyspace.mesh_active
    threads = []
    for h in (a, b):
        t = threading.Thread(target=h._server.serve_forever, daemon=True)
        t.start()
        threads.append(t)
    try:
        before = b.node.metrics._counts.get("merge_dispatches", 0)
        body = {f"k{i}": f"v{i}" for i in range(16)}
        req = urllib.request.Request(
            a.url + "/data", data=json.dumps(body).encode(), method="POST")
        req.add_header(TENANT_HEADER, "t-acme")
        assert urllib.request.urlopen(req, timeout=5).status == 200
        assert b.agent.ks_pull(RemotePeer(a.url)) == 16
        assert b.keyspace.tenant_state("t-acme") == body
        # one fused dispatch for the whole pull round
        assert (b.node.metrics._counts["merge_dispatches"] - before) == 1
        text = RemotePeer(b.url).metrics_text()
        for i in range(N_SHARDS):
            assert f'crdt_merge_dispatches_total{{shard="{i}"}}' in text
            assert (f'crdt_union_path_total{{path="sort",shard="{i}"}}'
                    in text)
        # the label-free fused tick serves alongside the labeled ones
        assert re.search(r"^crdt_merge_dispatches_total \d", text,
                         re.MULTILINE)
    finally:
        for h in (a, b):
            h._server.shutdown()
            h._server.server_close()
