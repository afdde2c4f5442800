"""The reference's own traffic on the unsharded kv plane
(anuragsarkar97/crdt ``main.go:273-314``): writes of one random alphabet
key with a delta in [delta_min, delta_max] to a uniformly random replica,
plus readers polling ``GET /data`` of the whole map.

Open loop.  The window holds exactly ``round(write_rate_per_s * seconds)``
writes at times drawn uniformly over the window (a Poisson process given
its count), so every seed sends the same work in another order.  Each of
``readers`` readers reads its own replica every ``read_period_ms`` from a
seeded phase.  Every write's acknowledgement carries its op ident
(``X-CRDT-Session-Token``); a poller reads every replica's ``GET /vv``
every ``vv_poll_ms`` and marks a write visible once every replica covers
it.  After the window the poller runs on for at most ``drain_s``.
"""
from __future__ import annotations

import asyncio
import bisect
import json
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import httpc

TOKEN = "x-crdt-session-token"


def plan(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Dict[str, Any]:
    dep = config["deployment"]
    return {
        "seed": int(seed),
        "replicas": int(dep["replicas"]),
        "alphabet": str(config["keys"]["alphabet"]),
        "delta_min": int(config["keys"]["delta_min"]),
        "delta_max": int(config["keys"]["delta_max"]),
        "rate": float(traffic["write_rate_per_s"]),
        "readers": int(traffic["readers"]),
        "read_period_s": float(traffic["read_period_ms"]) / 1e3,
        "vv_poll_s": float(traffic["vv_poll_ms"]) / 1e3,
        "drain_s": float(traffic["drain_s"]),
        "request_timeout_s": float(traffic["request_timeout_s"]),
        "max_inflight": int(traffic["max_inflight"]),
        "warm_batches": [int(b) for b in traffic["warm_batches"]],
        "capacity": int(dep["cluster"]["log_capacity"]),
    }


def schedule(p: Dict[str, Any], seconds: float) -> Dict[str, np.ndarray]:
    """The window's writes: due offset, replica, key index, delta."""
    n = int(round(p["rate"] * seconds))
    rng = np.random.default_rng([p["seed"] % (1 << 63), 2])
    return {
        "due": np.sort(rng.uniform(0.0, seconds, n)),
        "replica": rng.integers(0, p["replicas"], n),
        "key": rng.integers(0, len(p["alphabet"]), n),
        "delta": rng.integers(p["delta_min"], p["delta_max"] + 1, n),
        "phase": rng.uniform(0.0, p["read_period_s"], p["readers"]),
    }


def lead_s(p: Dict[str, Any]) -> float:
    """Open loop from the window's first instant: nothing runs before it."""
    return 0.0


# ---------------------------------------------------------------- server side

def preload(cluster, p: Dict[str, Any]) -> Dict[str, Any]:
    """The reference deployment starts empty."""
    return {"resident_field_rows": 0, "rows_per_shard": []}


def warm(cluster, p: Dict[str, Any]) -> None:
    from benchmark.cluster import warm_merges

    # readers start on an empty store: every key table up to the alphabet
    n = len(p["alphabet"])
    warm_merges(p["capacity"], p["warm_batches"], [n // 4, n // 2, n])


# ---------------------------------------------------------------- client side

class Visibility:
    """When every replica's version vector first covers each write."""

    def __init__(self, replicas: int):
        self.n = replicas
        self.vv: List[Dict[int, int]] = [{} for _ in range(replicas)]
        # per replica, per writer: sorted (seq, write index) not yet seen
        self.pending: List[Dict[int, list]] = [{} for _ in range(replicas)]
        self.seen: Dict[int, List[float]] = {}   # write -> per-replica time
        self.visible: Dict[int, float] = {}

    def _see(self, i: int, r: int, t: float) -> None:
        times = self.seen.setdefault(i, [None] * self.n)
        times[r] = t
        if all(x is not None for x in times):
            self.visible[i] = max(times)
            del self.seen[i]

    def on_ack(self, i: int, rid: int, seq: int, t: float) -> None:
        for r in range(self.n):
            if seq <= self.vv[r].get(rid, -1):
                self._see(i, r, t)
            else:
                bisect.insort(self.pending[r].setdefault(rid, []), (seq, i))

    def on_vv(self, r: int, vv: Dict[int, int], t: float) -> None:
        self.vv[r] = vv
        for rid, lst in self.pending[r].items():
            top = vv.get(rid, -1)
            k = 0
            while k < len(lst) and lst[k][0] <= top:
                self._see(lst[k][1], r, t)
                k += 1
            del lst[:k]

    def outstanding(self) -> int:
        return sum(len(lst) for per in self.pending for lst in per.values())


async def drive(p: Dict[str, Any], urls: List[str], t0: float,
                seconds: float) -> Dict[str, Any]:
    s = schedule(p, seconds)
    n = len(s["due"])
    addrs = [httpc.split(u) for u in urls]
    tmo = p["request_timeout_s"]
    sem = asyncio.Semaphore(p["max_inflight"])
    ack = [None] * n          # acknowledgement offset (a 200)
    done = [None] * n         # answer or failure offset
    status = [0] * n
    ident = [None] * n
    late = [0.0] * n          # how late the generator issued (before any
                              # wait for a connection slot)
    vis = Visibility(p["replicas"])
    reads: List[List[float]] = []   # [reader, due, done, status]
    alphabet = p["alphabet"]
    now = time.monotonic

    async def write(i: int) -> None:
        body = json.dumps({alphabet[s["key"][i]]: str(int(s["delta"][i]))})
        late[i] = now() - (t0 + s["due"][i])
        async with sem:
            try:
                st, hdr, _ = await httpc.request(
                    addrs[s["replica"][i]], "POST", "/data", body.encode(),
                    {"Content-Type": "application/json"}, timeout=tmo)
            except (OSError, asyncio.TimeoutError, ValueError):
                st, hdr = -1, {}
        t = now()
        status[i] = st
        done[i] = t - t0
        if st != 200:
            return
        ack[i] = t - t0
        tok = json.loads(hdr[TOKEN])
        (rid, seq), = ((int(k), int(v)) for k, v in tok.items())
        ident[i] = (rid, seq)
        vis.on_ack(i, rid, seq, t)

    async def read(r: int, due: float) -> None:
        try:
            st, _, body = await httpc.request(addrs[r], "GET", "/data",
                                              timeout=tmo)
            json.loads(body)
        except (OSError, asyncio.TimeoutError, ValueError):
            st = -1
        reads.append([r, due, now() - t0, st])

    async def reader(r: int, tasks: list) -> None:
        k = 0
        while True:
            due = s["phase"][r] + k * p["read_period_s"]
            if due >= seconds:
                return
            delay = t0 + due - now()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(read(r, due)))
            k += 1

    async def poll_once(r: int) -> None:
        try:
            st, _, body = await httpc.request(addrs[r], "GET", "/vv",
                                              timeout=tmo)
        except (OSError, asyncio.TimeoutError, ValueError):
            return
        if st == 200:
            vv = {int(k): int(v) for k, v in json.loads(body)["vv"].items()}
            vis.on_vv(r, vv, now())

    polling = asyncio.Event()

    async def poller() -> None:
        while not polling.is_set():
            t = now()
            await asyncio.gather(*(poll_once(r) for r in range(p["replicas"])))
            await asyncio.sleep(max(0.0, p["vv_poll_s"] - (now() - t)))

    delay = t0 - now()
    if delay > 0:
        await asyncio.sleep(delay)
    poll_task = asyncio.ensure_future(poller())
    read_tasks: list = []
    readers = [asyncio.ensure_future(reader(r, read_tasks))
               for r in range(p["readers"])]
    writes = []
    for i in range(n):
        delay = t0 + s["due"][i] - now()
        if delay > 0:
            await asyncio.sleep(delay)
        writes.append(asyncio.ensure_future(write(i)))
    await asyncio.gather(*readers)
    await asyncio.gather(*writes, *read_tasks)
    t_close = now()
    # drain: gossip runs on until every acknowledged write is visible (the
    # stale-read control reads at once instead)
    drain = 0.0 if p.get("control") == "stale_read" else p["drain_s"]
    while vis.outstanding() and now() - t_close < drain:
        await asyncio.sleep(p["vv_poll_s"])
    t_drained = now()
    polling.set()
    await poll_task
    return {
        "writes": {
            "due": s["due"].tolist(), "ack": ack, "done": done,
            "status": status,
            "replica": s["replica"].tolist(), "key": s["key"].tolist(),
            "delta": s["delta"].tolist(), "ident": ident, "late": late,
            "visible": [None if i not in vis.visible else vis.visible[i] - t0
                        for i in range(n)],
        },
        "reads": reads,
        "close_s": t_close - t0,
        "drained_s": t_drained - t0,
    }


# ---------------------------------------------------------------- judging

def p95(xs: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(xs)
    return v[max(0, -(-95 * len(v) // 100) - 1)]


def judge(cluster, p: Dict[str, Any], client: Dict[str, Any], seconds: float,
          control: str = "") -> Dict[str, Any]:
    from benchmark.oracle import OracleReplica, converged_state, mismatches

    w = client["writes"]
    n = len(w["due"])
    ok = [w["status"][i] == 200 for i in range(n)]
    # a failed request counts until the client gave up on it, a write not
    # visible when the drain ended until that end: both fail too, so they
    # sit in the tail and in ``failed``
    write_ms = [(w["done"][i] - w["due"][i]) * 1e3 for i in range(n)]
    vis_ms = [((w["visible"][i] if w["visible"][i] is not None
                else client["drained_s"]) - w["ack"][i]) * 1e3
              for i in range(n) if ok[i]]
    reads = client["reads"]
    read_ms = [(done - due) * 1e3 for _, due, done, _ in reads]
    # the reference: every acknowledged write, folded per replica that
    # took it; a write that failed without an answer (transport error)
    # may or may not have landed, so its key is left out of the check
    refs = [OracleReplica(rid=r) for r in range(p["replicas"])]
    unknown = set()
    alphabet = p["alphabet"]
    for i in range(n):
        key = alphabet[w["key"][i]]
        if ok[i]:
            rid, seq = w["ident"][i]
            refs[w["replica"][i]].add((0, rid, seq), {key: str(w["delta"][i])})
        elif w["status"][i] < 0:
            unknown.add(key)
    want = converged_state(refs)
    waited = None if control == "stale_read" else cluster.quiesce(p["drain_s"])
    bad = 0
    for r in range(cluster.n):
        bad += mismatches(cluster.get(r, "/data"), want, unknown)
    never = sum(1 for i in range(n) if ok[i] and w["visible"][i] is None)
    failed_w = n - sum(ok)
    failed_r = sum(1 for r in reads if r[3] != 200)
    late = sorted(w["late"])
    return {
        # BENCHMARK.json names the ones the cell reports; the tails are
        # also printed as facts on every run
        "metrics": {"write_p95_ms": p95(write_ms), "read_p95_ms": p95(read_ms),
                    "visible_p95_ms": p95(vis_ms)},
        "attempted": n + len(reads),
        "failed": failed_w + failed_r + never,
        "checks": {"mismatched_keys": (bad, 0)},
        "facts": {"writes": n, "reads": len(reads), "failed_writes": failed_w,
                  "failed_reads": failed_r, "never_visible": never,
                  "generator_late_p95_ms": p95([x * 1e3 for x in late]),
                  "write_p50_ms": sorted(write_ms)[n // 2] if n else None,
                  "write_p95_ms": p95(write_ms), "read_p95_ms": p95(read_ms),
                  "quiesce_s": waited},
    }
