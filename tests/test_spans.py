"""The served path's span contract: a two-NodeHost cluster, captured
with jax.profiler and read back by the benchmark's own trace reduction,
shows every span the per-layer readers (benchmark/layer_metrics) read —
the HTTP handlers, the admission wait, a contended node lock, the gossip
serves, the agent's round, the audit upkeep and a collection — and the
admission wait nests inside its POST /data handler."""
import gc
import json
import threading
import time
import urllib.request

import jax

from benchmark import trace as tracelib
from crdt_tpu.api.net import NodeHost
from crdt_tpu.ingest import PageBuilder
from crdt_tpu.keyspace import TENANT_HEADER
from crdt_tpu.utils.config import ClusterConfig

# every span name a reader in benchmark/layer_metrics reads
READ = ("crdt.http.post_data", "crdt.http.post_ingest_page",
        "crdt.wait.admit", "crdt.wait.node_lock", "crdt.http.get_gossip",
        "crdt.http.get_ks_gossip", "crdt.agent.round", "crdt.py_gc",
        "crdt.audit.observe", "crdt.audit.digest_at", "crdt.audit.evaluate")


def _post(url, data: bytes, tenant=None):
    req = urllib.request.Request(url, data=data, method="POST")
    if tenant is not None:
        req.add_header(TENANT_HEADER, tenant)
    with urllib.request.urlopen(req, timeout=60) as res:
        assert res.status == 200
        return res.read()


def _page(origin: int, n: int) -> bytes:
    pager = PageBuilder(origin=origin, page_size=1 << 16)
    for i in range(n):
        pager.add(f"k{origin}-{i}", f"v{i}")
    return pager.flush()


def test_served_path_spans(tmp_path):
    cfg = ClusterConfig(keyspace_shards=2, keyspace_capacity=256,
                        gossip_period_ms=20, audit_eval_every=1)
    a = NodeHost(rid=0, peers=[], config=cfg)
    b = NodeHost(rid=1, peers=[a.url], config=cfg)
    for h in (a, b):
        h.start_server()
    try:
        # warm every program the captured requests and round run
        _post(a.url + "/data", json.dumps({"w": "0"}).encode())
        _post(a.url + "/ingest/page", _page(1, 8), tenant="t-acme")
        b.agent.gossip_once()

        logdir = tmp_path / "trace"
        with jax.profiler.trace(str(logdir)):
            _post(a.url + "/data", json.dumps({"k": "1"}).encode())
            _post(a.url + "/ingest/page", _page(2, 8), tenant="t-acme")
            # a write queued behind a holder of the node lock
            writer = threading.Thread(target=_post, args=(
                a.url + "/data", json.dumps({"k": "2"}).encode()))
            with a.node._lock:
                writer.start()
                time.sleep(0.3)
            writer.join(timeout=60)
            assert not writer.is_alive()
            # one agent round of b: pulls a's /gossip and /ks/gossip
            evals = b.agent.watchdog.evals
            b.agent.start()
            deadline = time.monotonic() + 60
            while b.agent.watchdog.evals == evals \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            b.agent.stop()
            assert b.agent.watchdog.evals > evals
            gc.collect(2)
        files = list(logdir.rglob("*.xplane.pb"))
        assert len(files) == 1
        spans = tracelib.extract(str(files[0]))["spans"]
    finally:
        for h in (a, b):
            h.stop_server()
    names = {name for _, _, name in spans}
    assert set(READ) <= names, sorted(set(READ) - names)
    writes = [(s, e) for s, e, n in spans if n == "crdt.http.post_data"]
    waits = [(s, e) for s, e, n in spans if n == "crdt.wait.admit"]
    assert len(writes) == 2
    for ws, we in writes:
        assert any(ws <= s and e <= we for s, e in waits)
