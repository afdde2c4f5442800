"""The system under test: a fleet of NodeHosts in this process (a chip
belongs to one process), each serving HTTP and, from the window on,
running its own gossip agent thread as the deployed daemon does.

Only what a user or operator has is used: ``NodeHost`` and
``ClusterConfig``, the HTTP routes, and public node / keyspace methods
for the bulk preload, warm-up and version vectors.
"""
from __future__ import annotations

import json
import socket
import time
import urllib.request
from typing import Any, Dict, List, Optional

TENANT_HEADER = "X-CRDT-Tenant"


def free_ports(n: int) -> List[int]:
    """``n`` distinct ports the OS hands out now; NodeHost binds them
    right after, so every agent is built with its peers' URLs the way a
    daemon started with ``--peers`` is."""
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


class Cluster:
    """``config["deployment"]["replicas"]`` NodeHosts built from one
    ``ClusterConfig(**config["deployment"]["cluster"])``."""

    def __init__(self, config: Dict[str, Any]):
        from crdt_tpu.api.net import NodeHost
        from crdt_tpu.utils.config import ClusterConfig

        dep = config["deployment"]
        self.n = int(dep["replicas"])
        # the deployment's own seed (the config file pins it) drives the
        # agents' peer choices; the run's seed draws only the inputs, so
        # every seed meets the same peer-choice stream.  That stream alone
        # moves visible_p95_ms by ~2x (PERF.md s2): a change to the agent's
        # draws is a change to the yardstick
        self.cfg = ClusterConfig(**dep["cluster"])
        ports = free_ports(self.n)
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        self.hosts = [
            NodeHost(rid=r, peers=[u for j, u in enumerate(urls) if j != r],
                     port=ports[r], config=self.cfg)
            for r in range(self.n)
        ]
        for h in self.hosts:
            h.start_server()
        self.urls = [h.url for h in self.hosts]
        self._agents = False

    # ---- lifecycle ----

    def gossip_round(self) -> None:
        """One pull round per replica (``NetworkAgent.gossip_once``): warms
        the programs every round runs besides the op-log merge (the sibling
        lattices' pulls, e.g. the composite fold)."""
        for h in self.hosts:
            h.agent.gossip_once()

    def start_agents(self) -> None:
        for h in self.hosts:
            h.agent.start()
        self._agents = True

    def stop_agents(self) -> None:
        """Stop every gossip agent, leaving the servers up."""
        for h in self.hosts:
            h.agent.stop()

    def stop(self) -> List[str]:
        """Stop every agent and server; returns the gossip-loop errors
        (a dead loop is reported, not raised: the run still prints)."""
        errors = []
        for h in self.hosts:
            loop = h.agent._thread  # stop() forgets it after a 5 s join
            try:
                h.stop() if self._agents else h.stop_server()
            except RuntimeError as e:
                errors.append(f"replica {h.node.rid}: {e!r} <- {e.__cause__!r}")
            if loop is not None:
                loop.join(timeout=120)
        return errors

    # ---- reads an operator has ----

    def counter(self, name: str) -> float:
        """Sum over replicas of one label-free counter of the registry
        that GET /metrics renders.  Read from the registry itself: the
        route's keyspace gauges materialize every shard (O(store)), which
        would put seconds of host work into the window's edges."""
        return sum(h.node.metrics.registry.counter_value(name) or 0
                   for h in self.hosts)

    def vvs(self) -> List[Any]:
        """Every replica's version vector(s): one per keyspace shard, or
        the single plane's."""
        out = []
        for h in self.hosts:
            ks = h.keyspace
            if ks is None:
                out.append([h.node.version_vector()])
            else:
                out.append([ks.version_vector(i) for i in range(ks.n_shards)])
        return out

    def quiesce(self, limit_s: float, poll_s: float = 0.25) -> Optional[float]:
        """Wait until every replica holds the same version vectors (the
        agents keep gossiping); seconds waited, or None past the limit."""
        t0 = time.monotonic()
        while True:
            v = self.vvs()
            if all(x == v[0] for x in v[1:]):
                return time.monotonic() - t0
            if time.monotonic() - t0 > limit_s:
                return None
            time.sleep(poll_s)

    def get(self, r: int, path: str, tenant: Optional[str] = None,
            timeout: float = 300.0):
        req = urllib.request.Request(self.urls[r] + path)
        if tenant is not None:
            req.add_header(TENANT_HEADER, tenant)
        with urllib.request.urlopen(req, timeout=timeout) as res:
            return json.loads(res.read())

    def log_rows(self) -> List[int]:
        """Rows held per plane (shard logs, or the single plane)."""
        out = []
        for h in self.hosts:
            planes = h.keyspace.shards if h.keyspace is not None else [h.node]
            out.extend(len(p._commands) for p in planes)
        return out


def warm_merges(capacity: int, batches: List[int], key_counts: List[int]
                ) -> None:
    """Compile the served merge at ``capacity`` for every ingest batch
    size in ``batches``, and the read path's rebuild for a store of each
    of ``key_counts`` keys (the node rounds its key table up to a power of
    two), by driving scratch ReplicaNodes through the public calls the
    served path makes: ``add_commands`` -> the donated merge, and on a
    node's first dispatch the flight recorder's cost analysis of that
    shape (obs/devtime samples later dispatches, so each shape gets a node
    of its own); ``get_state`` -> rebuild + materialize."""
    from crdt_tpu.api.node import ReplicaNode

    # a writer id per (capacity, batch): obs/devtime counts dispatches per
    # writer for the life of the process, so a reused id could miss its
    # sampled first dispatch when a process runs twice (the CPU tests)
    base = (1 << 20) + (capacity.bit_length() << 8)
    for i, b in enumerate(batches):
        node = ReplicaNode(rid=base + i, capacity=capacity)
        node.add_commands([{f"w{j}": "0"} for j in range(b)])
    big = max(batches)
    for n_keys in key_counts:
        node = ReplicaNode(rid=base + 255, capacity=capacity)
        for start in range(0, n_keys, big):  # in shapes warmed above
            node.add_commands([{f"w{j}": "0"} for j in
                               range(start, min(n_keys, start + big))])
        node.get_state()
