"""YCSB's load phase over the sharded multi-tenant keyspace tier.

Records follow the YCSB core workload (Cooper et al., SoCC 2010):
``fieldcount`` fields of ``fieldlength`` random bytes, keys
``"user" + fnvhash64(recordnumber)`` (``insertorder=hashed``).  Each field
is one tenant-scoped key ``user<hash>/f<j>`` holding an LWW string; a
record's tenant is ``hash % tenants`` and tenant ``i`` writes through its
home replica ``i % replicas``.

The resident records are preloaded, converged, on every replica during
set-up.  The window is a closed loop of one client per tenant: each
posts its tenant's next page of ``records_per_page`` hashed-order
records to ``POST /ingest/page`` as soon as the previous page is
acknowledged.  The seed moves the record-number space and draws the
values; it never changes how much is written.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import httpc, pages

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 1099511628211
LETTERS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ", np.uint8)
BLOCK = 4096  # record numbers hashed per step when filling tenant queues


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` (FNV-1a over the 8 little-endian bytes,
    then ``Math.abs`` of the signed result), vectorized."""
    v = v.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME)
    s = h.view(np.int64)
    return np.where(s == np.iinfo(np.int64).min, 0, np.abs(s))


def record_base(seed: int) -> int:
    """First record number of this seed's space (disjoint per seed)."""
    return (int(seed) % (1 << 31)) << 24


def values(seed: int, stream: Tuple[int, ...], n: int, length: int) -> List[str]:
    """``n`` random letter strings of ``length`` (never an integer, so
    every field is an LWW register), drawn from (seed, stream)."""
    rng = np.random.default_rng([int(seed) % (1 << 63), *stream])
    raw = LETTERS[rng.integers(0, len(LETTERS), (n, length))].tobytes()
    return [raw[i * length:(i + 1) * length].decode("ascii") for i in range(n)]


def field_keys(keynums: np.ndarray, fieldcount: int) -> List[str]:
    return [f"user{k}/f{j}" for k in keynums.tolist() for j in range(fieldcount)]


class TenantQueues:
    """The hashed-order records after the resident ones, dealt to their
    tenants in record-number order: tenant ``t``'s k-th record."""

    def __init__(self, seed: int, first: int, tenants: int):
        self.next = record_base(seed) + first
        self.tenants = tenants
        self.queues: List[List[int]] = [[] for _ in range(tenants)]

    def take(self, tenant: int, start: int, n: int) -> np.ndarray:
        q = self.queues[tenant]
        while len(q) < start + n:
            nums = np.arange(self.next, self.next + BLOCK, dtype=np.uint64)
            self.next += BLOCK
            keys = fnvhash64(nums)
            for t in range(self.tenants):
                self.queues[t].extend(keys[keys % self.tenants == t].tolist())
        return np.asarray(q[start:start + n], np.int64)


def plan(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    rec = config["records"]
    return {
        "seed": int(seed),
        "replicas": int(config["deployment"]["replicas"]),
        "tenants": int(config["deployment"]["tenants"]),
        "resident": int(rec["recordcount"]),
        "fieldcount": int(rec["fieldcount"]),
        "fieldlength": int(rec["fieldlength"]),
        "clients": int(traffic["clients"]),
        "ramp_s": float(traffic["ramp_s"]),
        "records_per_page": int(traffic["records_per_page"]),
        "page_timeout_s": float(traffic["page_timeout_s"]),
        "quiesce_s": float(traffic["quiesce_s"]),
        "warm_batches": [int(b) for b in traffic["warm_batches"]],
        "capacity": int(config["deployment"]["cluster"]["keyspace_capacity"]),
    }


def page_contents(p: Dict[str, Any], queues: TenantQueues, client: int,
                  page: int) -> Tuple[List[str], List[str]]:
    """Keys and values of ``client``'s page number ``page``."""
    n = p["records_per_page"]
    nums = queues.take(client % p["tenants"], page * n, n)
    keys = field_keys(nums, p["fieldcount"])
    vals = values(p["seed"], (1, client, page), len(keys), p["fieldlength"])
    return keys, vals


def resident(p: Dict[str, Any]) -> Tuple[np.ndarray, List[str], List[str]]:
    """(tenant per field op, keys, values) of the resident records."""
    nums = np.arange(p["resident"], dtype=np.uint64) + np.uint64(
        record_base(p["seed"]))
    keynums = fnvhash64(nums)
    keys = field_keys(keynums, p["fieldcount"])
    vals = values(p["seed"], (0,), len(keys), p["fieldlength"])
    tenant = np.repeat(keynums % p["tenants"], p["fieldcount"])
    return tenant, keys, vals


# ---------------------------------------------------------------- server side

def preload(cluster, p: Dict[str, Any]) -> Dict[str, Any]:
    """Mint every resident field op on its tenant's home replica through
    ``add_commands`` (one bulk call per shard), then hand each shard's
    delta to the other replicas through ``receive``: every replica ends
    set-up holding the same converged store."""
    from crdt_tpu.keyspace.shards import qualify

    tenant, keys, vals = resident(p)
    hosts = cluster.hosts
    n_rep = len(hosts)
    n_shards = hosts[0].keyspace.n_shards
    cmds: Dict[Tuple[int, int], List[Dict[str, str]]] = {}
    ks0 = hosts[0].keyspace
    for t, k, v in zip(tenant.tolist(), keys, vals):
        tn = f"t{t}"
        cmds.setdefault((t % n_rep, ks0.shard_of(tn, k)), []).append(
            {qualify(tn, k): v})
    for (home, shard), batch in sorted(cmds.items()):
        hosts[home].keyspace.shards[shard].add_commands(batch)
    for shard in range(n_shards):
        for r, h in enumerate(hosts):
            for home in range(n_rep):
                if home == r:
                    continue
                src = hosts[home].keyspace
                payload = src.gossip_payload(
                    shard, since=h.keyspace.version_vector(shard))
                h.keyspace.receive(shard, payload)
    rows = [len(s._commands) for s in hosts[0].keyspace.shards]
    return {"resident_field_rows": len(keys), "rows_per_shard": rows}


def warm(cluster, p: Dict[str, Any]) -> None:
    from benchmark.cluster import warm_merges

    # the tenant reads at the end fold shards of ~resident/shards keys
    n_shards = cluster.hosts[0].keyspace.n_shards
    keys = p["resident"] * p["fieldcount"] // n_shards
    warm_merges(p["capacity"], p["warm_batches"], [keys])


# ---------------------------------------------------------------- client side

def lead_s(p: Dict[str, Any]) -> float:
    """The clients start ``ramp_s`` before the window (the end of set-up),
    so the window sees the loop in its steady state, the clients out of
    step, and every page whose acknowledgement falls in it."""
    return p["ramp_s"]


async def drive(p: Dict[str, Any], urls: List[str], t0: float,
                seconds: float) -> Dict[str, Any]:
    """The closed loop, from ``ramp_s`` before the window to its end;
    returns one row per page sent (times relative to the window start)."""
    t_end = t0 + seconds
    t_start = t0 - p["ramp_s"]
    queues = TenantQueues(p["seed"], p["resident"], p["tenants"])
    rows: List[List[Any]] = []

    async def client(c: int) -> None:
        addr = httpc.split(urls[(c % p["tenants"]) % p["replicas"]])
        hdr = {"X-CRDT-Tenant": f"t{c % p['tenants']}",
               "Content-Type": "application/octet-stream"}
        n_ops = p["records_per_page"] * p["fieldcount"]
        page, body = 0, None
        while True:
            if body is None:
                keys, vals = page_contents(p, queues, c, page)
                body = pages.encode(c, page, page * n_ops, keys, vals)
            delay = t_start - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            sent = time.monotonic()
            if sent >= t_end:
                return
            req = asyncio.ensure_future(httpc.request(
                addr, "POST", "/ingest/page", body, hdr,
                timeout=p["page_timeout_s"]))
            # build the next page while this one is in flight
            keys, vals = page_contents(p, queues, c, page + 1)
            nxt = pages.encode(c, page + 1, (page + 1) * n_ops, keys, vals)
            admitted, status = 0, 0
            try:
                status, _, resp = await req
                if status == 200:
                    admitted = int(json.loads(resp).get("admitted", 0))
            except (OSError, asyncio.TimeoutError, ValueError):
                status = -1
            rows.append([c, page, sent - t0, time.monotonic() - t0, status,
                         admitted])
            page, body = page + 1, nxt

    await asyncio.gather(*(client(c) for c in range(p["clients"])))
    return {"pages": rows}


# ---------------------------------------------------------------- judging

def expected(p: Dict[str, Any], pages_rows: List[List[Any]]):
    """Per tenant: the reference view over the resident records plus every
    acknowledged page, and the keys of pages whose outcome is unknown."""
    from benchmark.oracle import OracleReplica

    n_ops = p["records_per_page"] * p["fieldcount"]
    refs = [OracleReplica(rid=t) for t in range(p["tenants"])]
    tenant, keys, vals = resident(p)
    for i, (t, k, v) in enumerate(zip(tenant.tolist(), keys, vals)):
        refs[t].add((0, -1, i), {k: v})
    unknown: List[set] = [set() for _ in range(p["tenants"])]
    queues = TenantQueues(p["seed"], p["resident"], p["tenants"])
    for c, page, _, _, status, admitted in sorted(pages_rows):
        t = c % p["tenants"]
        keys, vals = page_contents(p, queues, c, page)
        if status == 200 and admitted == n_ops:
            for i, (k, v) in enumerate(zip(keys, vals)):
                refs[t].add((1, c, page * n_ops + i), {k: v})
        else:
            unknown[t].update(keys)
    return [r.state() for r in refs], unknown


def read_plan(p: Dict[str, Any], replicas: int) -> List[Tuple[int, int]]:
    """Which replica reads which tenant at the end: every tenant on every
    replica, so each acknowledged write is read back from all N replicas
    the guarantee names.  (A tenant read folds every shard of the replica,
    O(store): the 24 reads took 51 s on the chip.)"""
    return [(r, t) for r in range(replicas) for t in range(p["tenants"])]


def judge(cluster, p: Dict[str, Any], client: Dict[str, Any], seconds: float,
          control: str = "") -> Dict[str, Any]:
    from benchmark.oracle import mismatches

    rows = client["pages"]
    n_ops = p["records_per_page"] * p["fieldcount"]
    acked_in_window = sum(a for _, _, _, ack, s, a in rows
                          if s == 200 and 0 <= ack <= seconds)
    failed_pages = [r for r in rows if not (r[4] == 200 and r[5] == n_ops)]
    waited = None if control == "stale_read" else cluster.quiesce(p["quiesce_s"])
    want, unknown = expected(p, rows)
    per_replica = [0] * cluster.n
    t = time.monotonic()
    reads = read_plan(p, cluster.n)
    for r, tn in reads:
        got = cluster.get(r, "/data", tenant=f"t{tn}")
        per_replica[r] += mismatches(got, want[tn], unknown[tn])
    bad = sum(per_replica)
    read_s = time.monotonic() - t
    return {
        "metrics": {"write_ops_per_s": acked_in_window / seconds},
        "attempted": len(rows) * n_ops,
        "failed": len(failed_pages) * n_ops,
        "checks": {"mismatched_keys": (bad, 0)},
        "facts": {"pages": len(rows), "failed_pages": len(failed_pages),
                  "acked_ops_per_5s": [
                      sum(a for _, _, _, ack, s, a in rows
                          if s == 200 and 5 * k <= ack < 5 * (k + 1))
                      for k in range(int(-(-seconds // 5)))],
                  "quiesce_s": waited, "check_reads_s": read_s,
                  "tenant_reads": len(reads),
                  "keys_checked": sum(len(want[tn]) for _, tn in reads),
                  "mismatched_keys_per_replica": per_replica},
    }
