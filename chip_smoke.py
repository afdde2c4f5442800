"""Chip smoke: the served CRDT store, end to end, on one TPU.

    python chip_smoke.py              # one chip: phases (a)-(c) below
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths only

One chip.  The deployment is the README's million-key multi-tenant tier:
the reference's 5 replicas (ClusterConfig.n_replicas), each a NodeHost
with keyspace_shards=4, all in THIS process (a chip belongs to one
process).

  (a) device check — anything but a TPU exits non-zero before any work;
  (b) served path — 2**20 distinct tenant-qualified keys (by default cut
      to 2**19, printed, see DEFAULT_KEYS) written through
      POST /ingest/page (X-CRDT-Tenant), split over the replicas, counter
      deltas and overwrites mixed as harness/workload.py mixes them;
      NetworkAgent.gossip_once rounds until every shard's version vectors
      agree; GET /data per tenant on every replica checked against the
      plain host oracle (crdt_tpu.oracle) over the same seeded writes;
      GET /audit digests agree.  Every shard log must sit on the TPU, no
      mesh fallback may occur (EngineFallback is an error here), and one
      more replica with keyspace_mesh="on" replays replica 0's page trace
      through the single-device vmap mesh step and must match it bit for
      bit.  Compilations after warm-up are counted and must be 0;
  (c) swarm engine — oplog_engine.plan over 10,240 lanes (BASELINE config
      5's 10K replicas, rounded to the 128-lane tile): the compiled Pallas
      columnar engine, bit-equal to the generic engine.

Four chips (--chips 4): the keyspace mesh fold (pjit over 4 devices, one
lane per chip) against keyspace_mesh="off", and oplog_columnar's
sharded_converge over a 4-device mesh against single-device converge.

Earlier lines carry set-up facts (phase wall seconds, compile seconds,
rows resident, dispatch counts, peak device bytes) — not benchmark
numbers.  The LAST line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
and is printed only when every phase passed.

--rehearse-cpu runs the same phases on the CPU (tiny sizes, Pallas in
interpret mode) for debugging; it never prints the result line and exits 3.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys
import time
import urllib.request
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))
TENANT_HEADER = "X-CRDT-Tenant"
N_REPLICAS = 5          # ClusterConfig.n_replicas default
N_SHARDS = 4            # keyspace_shards of each replica
N_TENANTS = 8
PAGE_OPS = 2048
TS0 = 10_000_000        # explicit op timestamps start ~3 h past each epoch
FULL_KEYS = 1 << 20     # the README's million-key tier
# The full 2**20-key run passed on one v5e in 1102.4 s of script wall
# (PR 21 chip run: warm-up 203 s, writes 350 s, gossip 224 s) — too close
# to the 1200 s a run is given, so the default is cut to half the keys.
# Widths, key skew, replica count and guarantees are unchanged.
DEFAULT_KEYS = FULL_KEYS // 2
CUT_REASON = ("the 2**20-key run took 1102.4 s of the 1200 s allowed "
              "(warm-up 203.2 s, writes 349.7 s, gossip 223.9 s)")
SWARM_LANES = 10_240    # BASELINE config 5: 10K replicas, 128-lane tiles
SWARM_CAP = 64


def say(*parts):
    print(*parts, flush=True)


class Failed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise Failed(what)


# ---------------------------------------------------------------- (a)

def device_check(chips: int, rehearse: bool):
    """The device before anything else; off-TPU is a failure unless the
    caller asked for a CPU rehearsal."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not rehearse:
        print(f"chip_smoke: JAX found no TPU (platform={platform!r}); "
              "refusing to run on a fallback", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        sys.exit(2)
    say(f"# device: platform={platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and
    their seconds through jax.monitoring."""

    def __init__(self):
        from jax._src import monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.event = BACKEND_COMPILE_EVENT
        self.n = 0
        self.seconds = 0.0
        self.names = []
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == self.event:
            self.n += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.n, self.seconds, len(self.names), self.cache_hits

    def since(self, mark):
        n0, s0, i0, h0 = mark
        return (self.n - n0, self.seconds - s0, self.names[i0:],
                self.cache_hits - h0)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------- (b)

def plan_writes(n_keys: int, seed: int):
    """The seeded write plan: one op per key, then a second op on a
    quarter of the keys — counters get another delta from ANY replica
    (active-active), registers another overwrite from their home replica
    (so per-key order never depends on two replicas' clock epochs).
    Values follow harness/workload.py: deltas in [-20, -11].  Returns
    ops as (replica, tenant, key, value, ts) with ts unique and rising."""
    import numpy as np

    rng = np.random.default_rng(seed)
    idx = np.arange(n_keys)
    tenant = idx % N_TENANTS
    home = rng.integers(0, N_REPLICAS, n_keys)
    counter = rng.random(n_keys) < 0.5
    v1 = rng.integers(-20, -10, n_keys)
    s1 = rng.integers(0, 1 << 30, n_keys)
    second = np.flatnonzero(rng.random(n_keys) < 0.25)
    who2 = np.where(counter[second],
                    rng.integers(0, N_REPLICAS, len(second)), home[second])
    v2 = rng.integers(-20, -10, len(second))
    s2 = rng.integers(0, 1 << 30, len(second))
    ops = []
    for i in idx.tolist():
        val = str(v1[i]) if counter[i] else f"v{s1[i]}"
        ops.append((int(home[i]), f"t{tenant[i]}", f"k{i}", val, TS0 + i))
    for j, i in enumerate(second.tolist()):
        val = str(v2[j]) if counter[i] else f"v{s2[j]}"
        ops.append((int(who2[j]), f"t{tenant[i]}", f"k{i}", val,
                    TS0 + n_keys + j))
    return ops


def oracle_states(ops):
    """The plain host oracle over the same writes: one OracleReplica per
    writer, folded to the converged state; returned per tenant."""
    from crdt_tpu.oracle import OracleReplica

    reps = [OracleReplica(rid=r) for r in range(N_REPLICAS)]
    for r, tenant, key, val, ts in ops:
        reps[r].add_command({f"{tenant}:{key}": val}, ts)
    state = OracleReplica.converged_state(reps)
    out = {f"t{t}": {} for t in range(N_TENANTS)}
    for qkey, val in state.items():
        tenant, _, key = qkey.partition(":")
        out[tenant][key] = val
    return out


def build_pages(ops):
    """Client-side pages per (replica, tenant) writer stream, in ts order."""
    from crdt_tpu.ingest.wire import PageBuilder

    builders = {}
    pages = {r: [] for r in range(N_REPLICAS)}
    for r, tenant, key, val, ts in ops:
        b = builders.get((r, tenant))
        if b is None:
            b = builders[(r, tenant)] = PageBuilder(
                origin=1000 * r + int(tenant[1:]), page_size=PAGE_OPS)
        raw = b.add(key, val, ts=ts)
        if raw is not None:
            pages[r].append((tenant, raw))
    for (r, tenant), b in sorted(builders.items()):
        raw = b.flush()
        if raw is not None:
            pages[r].append((tenant, raw))
    return pages


def http(url, data=None, tenant=None, timeout=600):
    req = urllib.request.Request(url, data=data, method="POST" if data
                                 is not None else "GET")
    if tenant is not None:
        req.add_header(TENANT_HEADER, tenant)
    if data is not None:
        req.add_header("Content-Type", "application/octet-stream")
    with urllib.request.urlopen(req, timeout=timeout) as res:
        return json.loads(res.read())


def post_pages(host, pages):
    admitted = 0
    for tenant, raw in pages:
        out = http(host.url + "/ingest/page", raw, tenant)
        check(not out.get("dup"), f"page to {host.url} deduped")
        admitted += out["admitted"]
    return admitted


def make_host(rid, cfg):
    from crdt_tpu.api.net import NodeHost

    h = NodeHost(rid=rid, peers=[], port=0, config=cfg)
    h.start_server()
    return h


def wire_peers(hosts):
    from crdt_tpu.api.net import RemotePeer

    for h in hosts:
        h.agent.peers = [RemotePeer(o.url, timeout=h.config.peer_timeout_s)
                         for o in hosts if o is not h]


def shard_vvs(host):
    return [host.keyspace.version_vector(i)
            for i in range(host.keyspace.n_shards)]


def shard_logs(host):
    import numpy as np

    from crdt_tpu.models import oplog

    out = []
    for s in host.keyspace.shards:
        n = int(oplog.size(s.log))
        out.append({c: np.asarray(getattr(s.log, c))[:n]
                    for c in oplog.COLUMNS})
    return out


def store_digests(host):
    from crdt_tpu.obs.audit import store_digest_hex

    return [store_digest_hex(s) for s in host.keyspace.shards]


def warm_shapes(cap, max_batch, key_tables, counter, plane):
    """Compile every shape the served phase will dispatch before it is
    counted: the donated ingest merge and its cost analysis, the mesh
    host's own vmap step and its lane stacking, rebuild, version vector
    and size at the shard capacity ``cap``, for every power-of-two batch
    (from oplog.MIN_BATCH) and key-table size the run can reach."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from crdt_tpu.models import oplog
    from crdt_tpu.obs import devtime
    
    mark = counter.mark()
    t0 = time.perf_counter()
    no_ops = {c: np.zeros(0, bool if c == "is_num" else np.int32)
              for c in oplog.COLUMNS}
    log = oplog.empty(cap)
    logs = jax.tree.map(lambda *xs: jnp.stack(xs), *[log] * N_SHARDS)
    b = oplog.MIN_BATCH
    while b <= max_batch:
        batch = oplog.from_host_ops(b, no_ops)
        merged, n = oplog.merge_checked_donating(oplog.empty(cap), batch)
        int(n)
        devtime._cost_for(oplog.merge_checked_donating, (merged, batch))
        cols = tuple(np.stack([getattr(batch, c)] * N_SHARDS)
                     for c in oplog.COLUMNS)
        digs = np.zeros((N_SHARDS, b, 4), np.uint32)
        _, nu, _ = plane._step_for(cap, b)(logs, cols, digs)
        jax.device_get(nu)
        b *= 2
    int(oplog.size(log))
    for nw in (8, 16):
        oplog.version_vector(log, n_writers=nw).block_until_ready()
    for nk in key_tables:
        jax.block_until_ready(oplog.rebuild(log, n_keys=nk))
    n, secs, _, hits = counter.since(mark)
    say(f"# warm-up: {n} compiles, {secs:.3f} s compiling "
        f"({hits} persistent-cache hits), wall {time.perf_counter() - t0:.3f}"
        " s")


def served_phase(args, dev, counter):
    import jax
    import numpy as np

    from crdt_tpu.models.oplog_engine import EngineFallback
    from crdt_tpu.models import oplog
    from crdt_tpu.utils.config import ClusterConfig

    n_keys = args.keys
    if n_keys == DEFAULT_KEYS < FULL_KEYS:
        say(f"# scale cut: {FULL_KEYS} -> {n_keys} keys because {CUT_REASON}"
            f"; --keys {FULL_KEYS} runs the full size")
    say(f"# served: {N_REPLICAS} replicas x {N_SHARDS} shards, "
        f"{n_keys} keys over {N_TENANTS} tenants (seed {args.seed})")
    t = time.perf_counter()
    ops = plan_writes(n_keys, args.seed)
    expect = oracle_states(ops)
    pages = build_pages(ops)
    say(f"# setup: {len(ops)} ops, {sum(map(len, pages.values()))} pages, "
        f"oracle + pages in {time.perf_counter() - t:.3f} s")
    per_shard = len(ops) / N_SHARDS
    cap = 1 << int(per_shard * 1.1).bit_length()
    # largest ingest batch: a gossip delta (one peer's share of a shard)
    max_batch = 1 << int(per_shard).bit_length()
    cfg = ClusterConfig(
        keyspace_shards=N_SHARDS, keyspace_capacity=cap,
        ingest_flush_ops=PAGE_OPS, ingest_high_water=1 << 20,
        peer_timeout_s=600.0, gossip_period_ms=10 ** 9, seed=args.seed)
    say(f"# shard capacity {cap} rows (7 int32/bool columns)")
    # boot: the five replicas, plus one with keyspace_mesh="on" that will
    # replay replica 0's page trace (same rid and shard epochs as replica
    # 0, so its logs and digests must come out bit-equal), one empty
    # gossip round each, then every merge shape the run can reach
    t = time.perf_counter()
    hosts = [make_host(r, cfg) for r in range(N_REPLICAS)]
    mesh_host = make_host(0, dataclasses.replace(cfg, keyspace_mesh="on"))
    for s, s0 in zip(mesh_host.keyspace.shards, hosts[0].keyspace.shards):
        s.clock.epoch_ms = s0.clock.epoch_ms
    for h in hosts:
        check(not h.keyspace.mesh_active,
              "keyspace_mesh=auto fused on one chip")
    check(mesh_host.keyspace.mesh_engine == "vmap",
          f"mesh engine {mesh_host.keyspace.mesh_engine} on one chip")
    wire_peers(hosts)
    for h in hosts:
        h.agent.gossip_once()
    say(f"# boot: {N_REPLICAS + 1} NodeHosts in "
        f"{time.perf_counter() - t:.3f} s")
    # a shard interns ~n_keys / N_SHARDS keys; its key table is the next
    # power of two of that count (ReplicaNode._n_keys)
    p = 1 << (n_keys // N_SHARDS - 1).bit_length()
    warm_shapes(cap, max_batch, (p // 2, p, 2 * p), counter,
                mesh_host.keyspace._plane())

    with warnings.catch_warnings():
        warnings.simplefilter("error", EngineFallback)
        mark = counter.mark()
        t = time.perf_counter()
        admitted = sum(post_pages(h, pages[r]) for r, h in enumerate(hosts))
        check(admitted == len(ops), f"admitted {admitted} != {len(ops)}")
        t_write = time.perf_counter() - t
        say(f"# write: {admitted} ops admitted through /ingest/page in "
            f"{t_write:.3f} s")
        snap0 = (shard_logs(hosts[0]), shard_vvs(hosts[0]),
                 store_digests(hosts[0]))

        # the mesh-on replica: replica 0's page trace through the vmap step
        t = time.perf_counter()
        d0 = mesh_host.node.metrics.registry.counter_value("merge_dispatches")
        post_pages(mesh_host, pages[0])
        check(mesh_host.node.metrics.registry.counter_value(
            "merge_dispatches") - d0 == len(pages[0]),
            "mesh replica did not fold each page in one dispatch")
        logs_m = shard_logs(mesh_host)
        for i, (a, b) in enumerate(zip(snap0[0], logs_m)):
            for c in a:
                check(np.array_equal(a[c], b[c]),
                      f"mesh replica shard {i} column {c} differs")
        check(shard_vvs(mesh_host) == snap0[1], "mesh replica vv differs")
        check(store_digests(mesh_host) == snap0[2],
              "mesh replica digests differ")
        say(f"# mesh-on replica: page trace replayed bit-equal in "
            f"{time.perf_counter() - t:.3f} s")

        t = time.perf_counter()
        rounds = 0
        while True:
            vvs = [shard_vvs(h) for h in hosts]
            if all(v == vvs[0] for v in vvs[1:]):
                break
            check(rounds < 40, "no convergence after 40 gossip rounds")
            for h in hosts:
                h.agent.gossip_once()
            rounds += 1
        t_gossip = time.perf_counter() - t
        say(f"# gossip: converged in {rounds} rounds x {N_REPLICAS} "
            f"replicas, {t_gossip:.3f} s")
        wire_peers(hosts + [mesh_host])
        t = time.perf_counter()
        for peer in mesh_host.agent.peers:
            mesh_host.agent.ks_pull(peer)
        check(shard_vvs(mesh_host) == shard_vvs(hosts[0]),
              "mesh replica did not converge through its mesh pulls")
        say(f"# mesh-on replica: converged through mesh ks_pull in "
            f"{time.perf_counter() - t:.3f} s")

        t = time.perf_counter()
        checked = 0
        for r, h in enumerate(hosts + [mesh_host]):
            for tenant in (f"t{(2 * r) % N_TENANTS}",
                           f"t{(2 * r + 1) % N_TENANTS}"):
                got = http(h.url + "/data", tenant=tenant)
                check(got == expect[tenant],
                      f"replica {r} GET /data tenant {tenant} != oracle "
                      f"({len(got)} vs {len(expect[tenant])} keys)")
                checked += len(got)
        t_read = time.perf_counter() - t
        say(f"# read: {checked} keys over GET /data equal the oracle on "
            f"{N_REPLICAS + 1} replicas in {t_read:.3f} s")
        reports = [http(h.url + "/audit") for h in hosts + [mesh_host]]
        for plane in reports[0]["planes"]:
            if not plane.startswith("ks-"):
                continue
            digs = {rep["planes"][plane]["digest"] for rep in reports}
            check(len(digs) == 1, f"GET /audit {plane} digests disagree")
        full = {tuple(store_digests(h)) for h in hosts + [mesh_host]}
        check(len(full) == 1, "full-store shard digests disagree")
        say("# audit: GET /audit and full-store digests agree on every "
            "replica")

        n, secs, names, hits = counter.since(mark)
        say(f"# compiles during the served phase after warm-up: {n} "
            f"({secs:.3f} s) {sorted(set(names))}")
        check(n == 0, f"served phase compiled {n} programs: {names}")

    want = dev.platform
    rows = 0
    for h in hosts + [mesh_host]:
        for s in h.keyspace.shards:
            rows = max(rows, int(oplog.size(s.log)))
            for leaf in jax.tree.leaves(s.log):
                check({d.platform for d in leaf.devices()} == {want},
                      f"shard log leaf on {leaf.devices()}")
    fallbacks = sum(h.node.metrics.registry.counter_value(
        "meshplane_fallbacks") or 0 for h in hosts + [mesh_host])
    check(fallbacks == 0, f"meshplane_fallbacks={fallbacks}")
    dispatches = [h.node.metrics.registry.counter_value("merge_dispatches")
                  for h in hosts + [mesh_host]]
    say(f"# resident: {len(ops)} ops -> up to {rows} rows per shard log "
        f"({N_SHARDS} shards/replica, all on {want}); merge_dispatches "
        f"per replica {dispatches}; meshplane_fallbacks 0; "
        f"peak_bytes_in_use {peak_bytes(dev)}")
    for h in hosts + [mesh_host]:
        h.stop_server()


# ---------------------------------------------------------------- (c)

def random_swarm(lanes, cap, seed):
    """[lanes, cap] sorted op logs, each a random subset of one shared
    op pool (union fits cap, so converge is lossless)."""
    import numpy as np

    from crdt_tpu.models import oplog
    from crdt_tpu.utils.constants import SENTINEL

    rng = np.random.default_rng(seed)
    pool_n = cap - 8
    ident = set()
    while len(ident) < pool_n:
        ident.add((int(rng.integers(0, 1 << 16)), int(rng.integers(0, 16)),
                   int(rng.integers(0, 256)), int(rng.integers(0, 512))))
    pool = np.asarray(sorted(ident), np.int32)
    val = rng.integers(-20, 21, pool_n).astype(np.int32)
    pay = rng.integers(0, 1000, pool_n).astype(np.int32)
    isn = rng.random(pool_n) < 0.5
    take = rng.random((lanes, pool_n)) < 0.3
    order = np.argsort(~take, axis=1, kind="stable")
    n_take = take.sum(axis=1)
    live = np.arange(pool_n)[None, :] < n_take[:, None]

    def col(x, fill, dtype):
        out = np.full((lanes, cap), fill, dtype)
        out[:, :pool_n] = np.where(live, x[order], fill)
        return out

    s = SENTINEL
    return oplog.OpLog(
        ts=col(pool[:, 0], s, np.int32), rid=col(pool[:, 1], s, np.int32),
        seq=col(pool[:, 2], s, np.int32), key=col(pool[:, 3], s, np.int32),
        val=col(val, 0, np.int32), payload=col(pay, 0, np.int32),
        is_num=col(isn, False, bool))


def assert_trees_equal(a, b, what):
    import jax
    import numpy as np

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        check(np.array_equal(np.asarray(x), np.asarray(y)), what)


def swarm_phase(args, dev, counter):
    import numpy as np

    from crdt_tpu.models import oplog_engine as eng
    from crdt_tpu.models.oplog_engine import EngineFallback

    lanes = args.swarm_lanes
    state = random_swarm(lanes, SWARM_CAP, args.seed)
    mark = counter.mark()
    t = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("error", EngineFallback)
        fast = eng.plan(state)
    slow = eng.plan(state, force_generic=True)
    check(fast.engine == "columnar", f"swarm engine {fast.engine}")
    check(fast.interpret is (dev.platform != "tpu"),
          f"swarm interpret={fast.interpret} on {dev.platform}")
    peers = np.asarray((np.arange(lanes) + 7) % lanes, np.int32)
    fg, sg = fast.gossip_round(peers), slow.gossip_round(peers)
    assert_trees_equal(fg.rows(), sg.rows(), "gossip_round engines differ")
    fc, fnu = fg.converge_checked()
    sc, snu = sg.converge_checked()
    assert_trees_equal(fc.rows(), sc.rows(), "converge engines differ")
    check(int(fnu) == int(snu) <= SWARM_CAP, f"n_unique {fnu} vs {snu}")
    assert_trees_equal(fc.rebuild(512), sc.rebuild(512),
                       "rebuild engines differ")
    n, secs, _, _ = counter.since(mark)
    say(f"# swarm: {lanes} lanes x C={SWARM_CAP}, columnar (interpret="
        f"{fast.interpret}) == generic on gossip_round/converge/rebuild; "
        f"{n} compiles {secs:.3f} s, wall {time.perf_counter() - t:.3f} s, "
        f"peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------- 4 chips

def mesh4_phase(args, devs):
    """Keyspace mesh fold over 4 chips vs the host path, same trace."""
    import numpy as np

    from crdt_tpu.api.node import ReplicaNode
    from crdt_tpu.keyspace.shards import ShardedKeyspace, qualify
    from crdt_tpu.models import oplog
    from crdt_tpu.models.oplog_engine import EngineFallback
    from crdt_tpu.utils.clock import ManualClock
    from crdt_tpu.utils.metrics import Metrics

    t = time.perf_counter()
    clock = ManualClock()
    cap = args.mesh_capacity
    off = ShardedKeyspace(rid=0, n_shards=N_SHARDS, capacity=cap,
                          metrics=Metrics(), clock=clock, mesh="off")
    on = ShardedKeyspace(rid=0, n_shards=N_SHARDS, capacity=cap,
                         metrics=Metrics(), clock=clock, mesh="on")
    for ks in (off, on):
        ks.enable_audit()
    check(on.mesh_engine == "pjit", f"4-chip mesh engine {on.mesh_engine}")
    plane = on._plane()
    ids = [d.id for d in plane.mesh.devices.flat]
    say(f"# mesh4: keyspace mesh over devices {ids}")
    check(len(set(ids)) == N_SHARDS, f"mesh devices {ids}")
    writers = {(s, r): ReplicaNode(rid=r, capacity=cap, clock=clock)
               for s in range(N_SHARDS) for r in (100, 101)}
    rng = random.Random(args.seed)
    rounds = args.mesh_rounds
    per_round = args.mesh_ops // rounds
    with warnings.catch_warnings():
        warnings.simplefilter("error", EngineFallback)
        for _ in range(rounds):
            batches = {}
            for _ in range(per_round):
                tenant = f"t{rng.randrange(N_TENANTS)}"
                key = f"k{rng.randrange(args.mesh_ops)}"
                val = (str(rng.randint(-20, -11)) if rng.random() < 0.5
                       else f"v{rng.randrange(1 << 30)}")
                shard = on.shard_of(tenant, key)
                batches.setdefault((shard, rng.choice((100, 101))), []) \
                    .append({qualify(tenant, key): val})
            for wk, cmds in batches.items():
                writers[wk].add_commands(cmds)
            clock.advance(1)
            payloads = []
            for s in range(N_SHARDS):
                merged = {}
                for r in (100, 101):
                    p = writers[(s, r)].gossip_payload()
                    if p:
                        merged.update(p)
                payloads.append(merged or None)
            off.receive_all(payloads)
            on.receive_all(payloads)
            check(plane.last_devices == tuple(sorted(ids)),
                  f"mesh step inputs on devices {plane.last_devices}")
    for i, (h, m) in enumerate(zip(off.shards, on.shards)):
        check(m.get_state() == h.get_state(), f"mesh4 shard {i} state")
        check(m.version_vector() == h.version_vector(), f"mesh4 shard {i} vv")
        n = int(oplog.size(h.log))
        check(int(oplog.size(m.log)) == n, f"mesh4 shard {i} rows")
        for c in oplog.COLUMNS:
            check(np.array_equal(np.asarray(getattr(h.log, c))[:n],
                                 np.asarray(getattr(m.log, c))[:n]),
                  f"mesh4 shard {i} column {c}")
    check(store_digests_ks(on) == store_digests_ks(off), "mesh4 digests")
    fallbacks = on.metrics.registry.counter_value("meshplane_fallbacks") or 0
    check(fallbacks == 0, f"meshplane_fallbacks={fallbacks}")
    say(f"# mesh4: {rounds} fused rounds, {args.mesh_ops} ops, step inputs "
        f"on devices {list(plane.last_devices)}; bit-equal to "
        f"keyspace_mesh=off (state, vv, all columns, digests) in "
        f"{time.perf_counter() - t:.3f} s")


def store_digests_ks(ks):
    from crdt_tpu.obs.audit import store_digest_hex

    return [store_digest_hex(s) for s in ks.shards]


def sharded_converge_phase(args, devs):
    """oc.sharded_converge over a 4-device mesh vs single-device converge."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from crdt_tpu.models import oplog_columnar as oc
    from crdt_tpu.models import oplog_engine as eng

    t = time.perf_counter()
    state = random_swarm(args.swarm_lanes, SWARM_CAP, args.seed + 1)
    bits, reason = eng.columnar_plan(state)
    check(bits is not None, f"swarm not columnar: {reason}")
    col = oc.stack(state, bits=bits)
    alive = np.ones(args.swarm_lanes, bool)
    alive[::97] = False
    ref, ref_nu = oc.converge_checked(
        col, alive, interpret=jax.default_backend() != "tpu")
    mesh = Mesh(np.asarray(devs[:4]), ("replica",))
    step = oc.sharded_converge(mesh, bits=bits)
    sh = NamedSharding(mesh, P(None, "replica"))
    col_s = jax.device_put(col, sh)
    n_dev = len(col_s.hi.sharding.device_set)
    check(n_dev == 4, f"sharded swarm spans {n_dev} devices")
    out, nu = step(col_s, jax.device_put(alive, NamedSharding(mesh,
                                                              P("replica"))))
    assert_trees_equal(oc.unstack(out), oc.unstack(ref),
                       "sharded_converge != converge")
    check(int(nu) == int(ref_nu), f"n_unique {nu} vs {ref_nu}")
    say(f"# sharded_converge: {args.swarm_lanes} lanes over devices "
        f"{sorted(d.id for d in col_s.hi.sharding.device_set)} bit-equal to "
        f"single-device converge in {time.perf_counter() - t:.3f} s")


# ---------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS,
                    help=f"distinct keys (full size {FULL_KEYS})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--swarm-lanes", type=int, default=SWARM_LANES)
    ap.add_argument("--mesh-ops", type=int, default=1 << 16)
    ap.add_argument("--mesh-rounds", type=int, default=8)
    ap.add_argument("--mesh-capacity", type=int, default=1 << 15)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug on the CPU; never prints the result line")
    args = ap.parse_args(argv)
    if args.rehearse_cpu and args.chips == 4:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=4")
    devs = device_check(args.chips, args.rehearse_cpu)
    sys.path.insert(0, REPO)
    from crdt_tpu.utils import compile_cache

    say(f"# compile cache: {compile_cache.enable()}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            for name, phase in (("mesh4", mesh4_phase),
                                ("sharded_converge", sharded_converge_phase)):
                t = time.perf_counter()
                phase(args, devs)
                say(f"# phase {name}: {time.perf_counter() - t:.3f} s")
        else:
            for name, phase in (("served", served_phase),
                                ("swarm", swarm_phase)):
                t = time.perf_counter()
                phase(args, devs[0], counter)
                say(f"# phase {name}: {time.perf_counter() - t:.3f} s")
    except Failed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    say(f"# compile: {counter.n} compiles, {counter.seconds:.3f} s in "
        f"backend compile, {counter.cache_hits} persistent-cache hits; "
        f"total wall {time.perf_counter() - t0:.3f} s")
    if args.rehearse_cpu:
        say("# rehearsal passed on the CPU; no result line")
        return 3
    d = devs[0]
    say(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
