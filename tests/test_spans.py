"""The served path's span contract: a two-NodeHost cluster, captured
with jax.profiler and read back by the benchmark's own trace reduction,
shows every span the per-layer readers (benchmark/layer_metrics) read —
the HTTP handlers, the admission wait, a contended node lock, the gossip
serves, the agent's round, the audit upkeep, a collection, the merge's
device wait, accept and pack, and the interpreter-wait probe — and each
nests where its reader assumes: the admission wait inside its POST /data
handler, accept / pack / device wait inside a gossip receive or a drain.
A bare replica shows one device wait per merge dispatch, after it."""
import gc
import json
import threading
import time
import urllib.request

import jax
import pytest

from benchmark import trace as tracelib
from crdt_tpu.api.net import NodeHost
from crdt_tpu.api.node import ReplicaNode
from crdt_tpu.ingest import PageBuilder
from crdt_tpu.keyspace import TENANT_HEADER
from crdt_tpu.obs import trace
from crdt_tpu.utils.config import ClusterConfig

# every span name a reader in benchmark/layer_metrics reads
READ = ("crdt.http.post_data", "crdt.http.post_ingest_page",
        "crdt.wait.admit", "crdt.wait.node_lock", "crdt.http.get_gossip",
        "crdt.http.get_ks_gossip", "crdt.agent.round", "crdt.py_gc",
        "crdt.audit.observe", "crdt.audit.digest_at", "crdt.audit.evaluate",
        "crdt.wait.device", "crdt.accept", "crdt.pack", "crdt.probe.gil")


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


def _capture(logdir):
    """The crdt.* spans of the one capture under ``logdir``."""
    files = list(logdir.rglob("*.xplane.pb"))
    assert len(files) == 1
    return tracelib.extract(str(files[0]))["spans"]


@pytest.fixture(scope="module")
def served_spans(tmp_path_factory):
    """The spans of one captured stretch of a two-NodeHost cluster's
    work: writes, a page, a contended node lock, one agent round of b
    (pulls of a's /gossip and /ks/gossip, the watchdog), a collection."""
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

        logdir = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(logdir)):
            # the probe looks for a session once per PROBE_IDLE_S
            time.sleep(trace.PROBE_IDLE_S + 0.1)
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
        return _capture(logdir)
    finally:
        for h in (a, b):
            h.stop_server()


def _of(spans, name):
    return [(s, e) for s, e, n in spans if n == name]


def _inside(inner, outers) -> bool:
    s, e = inner
    return any(os <= s and e <= oe for os, oe in outers)


def test_served_path_spans(served_spans):
    spans = served_spans
    names = {name for _, _, name in spans}
    assert set(READ) <= names, sorted(set(READ) - names)
    writes = _of(spans, "crdt.http.post_data")
    waits = _of(spans, "crdt.wait.admit")
    assert len(writes) == 2
    for w in writes:
        assert any(_inside(x, [w]) for x in waits)


# a kv receive, the shard pulls (8 virtual devices: the mesh plane's
# fused receive, whose one sync is its device wait) and the lane drains
RECEIVES = ("crdt.merge", "crdt.ks_pull_mesh")


@pytest.mark.parametrize("part, holders", [
    ("crdt.accept", RECEIVES),
    ("crdt.pack", RECEIVES + ("crdt.drain",)),
    ("crdt.wait.device", RECEIVES + ("crdt.drain",)),
])
def test_merge_parts_nest(served_spans, part, holders):
    """Every accept, pack and device wait of the captured stretch lies
    inside a gossip receive or a lane drain, and each holder named here
    holds at least one of them: together with the audit upkeep they
    split ``crdt.merge`` and ``crdt.drain`` into their parts."""
    parts = _of(served_spans, part)
    assert parts
    outers = {h: _of(served_spans, h) for h in holders}
    for p in parts:
        assert any(_inside(p, o) for o in outers.values()), part
    for h, o in outers.items():
        assert any(_inside(p, o) for p in parts), (part, h)


@pytest.mark.parametrize("path", ["drain", "receive"])
def test_one_device_wait_per_merge_dispatch(tmp_path, path):
    """``_merge_batch`` opens exactly one ``crdt.wait.device`` per merge
    dispatch, around the host sync of the result: it starts after the
    dispatch's own ``crdt.join.merge`` span has closed."""
    src = ReplicaNode(rid=1, capacity=64)
    node = ReplicaNode(rid=0, capacity=64)
    src.add_commands([{f"s{i}": str(i)} for i in range(4)])
    node.add_commands([{"warm": "0"}])  # compile the merge outside

    def work():
        if path == "drain":
            node.add_commands([{f"k{i}": str(i)} for i in range(3)])
            node.add_commands([{"k9": "9"}])
        else:
            assert node.receive(src.gossip_payload()) == 4
            node.add_commands([{"k9": "9"}])

    before = node.metrics.registry.counter_value("merge_dispatches")
    logdir = tmp_path / "trace"
    with jax.profiler.trace(str(logdir)):
        work()
    dispatched = (node.metrics.registry.counter_value("merge_dispatches")
                  - before)
    spans = _capture(logdir)
    merges = sorted(_of(spans, "crdt.join.merge"))
    waits = sorted(_of(spans, "crdt.wait.device"))
    assert dispatched == len(merges) == len(waits) == 2
    for (_, merge_end), (wait_start, _) in zip(merges, waits):
        assert merge_end <= wait_start
