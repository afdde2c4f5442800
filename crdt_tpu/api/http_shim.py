"""HTTP shim: the reference's five-endpoint REST surface over ReplicaNodes,
for black-box parity testing against the Go server (SURVEY.md §2 #5/#10).

Routes (1:1 with /root/reference/main.go:262-266):
  GET  /gossip                  full op log as JSON        (main.go:154-171)
  GET  /ping                    200 "Pong" / 502           (main.go:115-127)
  GET  /data                    materialized state JSON    (main.go:129-139)
  POST /data                    append command, "Inserted" (main.go:173-215)
  GET  /condition/<bool>        set alive                  (main.go:141-152)

Framework extensions (not part of the Go surface; used by the cross-daemon
compaction barrier, crdt_tpu.api.net.network_compact):
  GET  /vv                      {"vv": {rid: seq}, "frontier": {rid: seq}}
  POST /compact                 {"frontier": {rid: seq}} -> fold + prune

Consistency plane (crdt_tpu.consistency; /read and /cas present only with
``admin`` — they need the NodeHost's ConsistencyPlane):
  GET  /read?key=k&level=l      l in eventual|session|bounded|
                                linearizable; a session read requires the
                                caller's token in the X-CRDT-Session-Token
                                request header; bounded accepts
                                &staleness=<Δ ops> (default from config).
                                200 {"key","value","level"};
                                503 {"error":"consistency_unavailable",...}
                                + Retry-After header when the level's
                                guarantee cannot be met (never a silently
                                stale value)
  POST /cas                     {"key","expect","update"} (expect null =
                                key must be absent) OR the multi-key form
                                {"ops": {key: {"expect","update"}}} (all
                                keys routed, all-or-nothing) -> 200
                                {"token"}, 409 {"conflict":true,"actual",
                                "coordinator","fence"} naming the deciding
                                coordinator so clients can re-route,
                                503 as /read ("indeterminate":true once
                                the write was minted but not quorum-acked).
                                With a LeaseManager the request routes to
                                the key's slot coordinator ("hops" in the
                                body counts forwards taken, bounded).
  POST /push                    {"payload": <gossip payload>} -> merge NOW
                                ("fresh": n): the synchronous write-quorum
                                leg of CAS.  An optional {"fences": {slot:
                                epoch}} stamp is checked BEFORE the merge:
                                a stale fence is refused whole — 409
                                {"fenced":true,"slot","fence"} — so a
                                zombie coordinator can never commit late
  POST /lease/grant             {"slot","holder","fence","ttl"} -> one
                                coordinator-lease vote ({"granted",
                                "fence","holder"}; a refusal names the
                                blocking fence/holder)
  POST /data additionally answers with an X-CRDT-Session-Token response
  header (the write's vv watermark, minted from the ingest ticket ident)
  when the node has an ingest front door; every GET /gossip response
  carries an X-CRDT-Stability header ({rid, vv, frontier}) — the
  piggyback that feeds the StabilityTracker with zero extra round trips.

Observability (crdt_tpu.obs):
  GET  /metrics                 Prometheus text exposition (counters,
                                gauges, latency histograms + the lattice
                                health gauges sampled at scrape time)
  GET  /gossip with an X-CRDT-Trace header records a gossip_serve event
  under the puller's trace ID in this node's event log and echoes the
  header back — one trace ID names the round on both ends of the wire.

Daemon admin extensions (present only when the handler is built with an
``admin`` object — a NodeHost; used by the crash soak to drive a daemon
fleet deterministically, crdt_tpu.harness.crashsoak):
  POST /admin/pull              {"peer": url?} -> one gossip pull now
  POST /admin/barrier           one compaction barrier now (coordinator)
  POST /admin/stability_gc      one stability-frontier GC round now
                                (coordinator; zero-round-trip barrier)
  POST /admin/checkpoint        crash-safe snapshot now
  POST /admin/set_pull          {"peer": url?} -> one set pull now
  POST /admin/set_barrier       one set GC barrier now (coordinator)
  POST /admin/map_pull          {"peer": url?} -> one map pull now
  POST /admin/map_barrier       one map reset barrier now (coordinator)
  POST /admin/composite_pull    {"peer": url?} -> one composite pull now

Set-lattice surface (crdt_tpu.api.setnode; present only with ``admin``):
  GET  /set                     {"members": [...]}
  GET  /set/gossip[?vv=...]     floor-carrying (delta) set payload
  GET  /set/vv                  {"vv": {rid: seq}, "floor": {rid: seq}}
  POST /set/add                 {"elem": str} -> mint one add op
  POST /set/remove              {"elem": str} -> observed-remove
  POST /set/collect             {"floor": {rid: seq}} -> GC fold

Sequence-lattice surface (crdt_tpu.api.seqnode; present only with
``admin``) — plus POST /admin/seq_pull and /admin/seq_barrier:
  GET  /seq                     {"items": [...]} (live list, in order)
  GET  /seq/gossip[?vv=...]     floor-carrying (delta) sequence payload
  GET  /seq/vv                  {"vv": {rid: seq}, "floor": {rid: seq}}
  POST /seq/insert              {"elem": str, "index": int|null} -> mint
  POST /seq/remove              {"index": int} -> targeted remove
  POST /seq/collect             {"floor": {rid: seq}} -> GC fold

Map-lattice surface (crdt_tpu.api.mapnode; present only with ``admin``
or a cluster carrying map siblings) — the concrete PN-composition map
with reset-wins epoch GC:
  GET  /map                     {"items": {key: value}}
  GET  /map/gossip[?vv=...]     epoch-carrying (delta) map payload
  GET  /map/vv                  {"vv": {rid: seq}, "epochs": {key: epoch}}
  POST /map/upd                 {"key": str, "delta": int} -> mint one op
  POST /map/rem                 {"key": str} -> observed-remove
  POST /map/reset               {"epochs": {key: epoch}} -> adopt reset

Composite surface (crdt_tpu.api.compositenode; present only with
``admin`` or a cluster carrying composite siblings) — the served
``mapof(pncounter)`` from the compositional algebra.  State-based: the
gossip payload is a full trimmed dump, no vv/delta negotiation and no
GC barrier (the algebra's idempotence + monotonicity ARE the protocol):
  GET  /composite               {"items": {key: value}}
  GET  /composite/gossip        full state dump (keys/writers + planes)
  POST /composite/upd           {"key": str, "delta": int} -> {"value"}
  POST /composite/rem           {"key": str} -> {"removed": bool}

Sharded keyspace surface (crdt_tpu.keyspace; present only with ``admin``
whose config enables keyspace_shards > 0).  Writes name their tenant in
the X-CRDT-Tenant request header: /data, /ingest/page and /map/upd with
the header route through the tenant door (rendezvous-sharded, per-tenant
quota); without the header they keep the single-plane path:
  GET  /ks/gossip?shard=i[&vv=] one SHARD's delta payload + its
             [&epoch=e]         stability summary in the body
                                ({"payload","vv","frontier"}); a stale
                                reshard epoch 409s naming the live one
  GET  /ks/data[?tenant=t]      tenant's materialized state, or the
                                per-shard stats without ?tenant
  POST /ks/compact              {"shard": i, "frontier": {rid: seq},
                                "epoch": e?} -> fold ONE shard (shard-
                                local GC); stale epoch 409s
  POST /ks/migrate              {"shard": dst, "epoch": e, "payload":
                                wire} -> fold one reshard migration
                                slice into the MIGRATE buffer; 409 when
                                not migrating at e, 400 quarantine on a
                                corrupt slice
  POST /admin/ks_pull           {"peer": url?} -> one keyspace pull now
  POST /admin/ks_gc             one shard-local stability-GC round now
                                (coordinator)
  POST /admin/ks_reshard        {"action": "start"|"stream"|"cutover"|
                                "abort"|"status", "shards": S'?} ->
                                drive the online-reshard state machine
                                (keyspace/reshard.py)
  (tenant-scoped POST /ingest/page may stamp X-CRDT-KS-Epoch; a stale
  stamp 409s instead of admitting against a moved shard map)

The /condition route takes the flag as a path segment (also accepted:
?alive_status=) — the reference registered the route without the parameter
binding so every call 500'd (quirk §0.1.7); this shim implements what that
endpoint was meant to do.
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional
from urllib.parse import parse_qs, urlparse

from crdt_tpu.api.cluster import LocalCluster
from crdt_tpu.consistency.plane import CasConflict, ConsistencyUnavailable
from crdt_tpu.consistency.session import (
    SESSION_TOKEN_HEADER,
    decode_token,
    encode_token,
)
from crdt_tpu.consistency.stability import STABILITY_HEADER, encode_summary
from crdt_tpu.ingest import PageFormatError, ShedError
from crdt_tpu.keyspace import TENANT_HEADER
from crdt_tpu.obs import health
from crdt_tpu.obs.trace import TRACE_HEADER, region, span

PROM_CTYPE = "text/plain; version=0.0.4; charset=utf-8"

# optional reshard-epoch stamp on tenant-scoped page admits: a stamped
# page 409s when the writer's epoch is stale (see keyspace/reshard.py);
# an un-stamped page routes by the live shard map, back-compatible
KS_EPOCH_HEADER = "X-CRDT-KS-Epoch"

# (method, path) -> the <route> of a handler's crdt.http.<route> span; a
# fixed map, so a span name never carries a request's own path
_ROUTES = {
    ("POST", "/data"): "post_data",
    ("POST", "/ingest/page"): "post_ingest_page",
    ("GET", "/data"): "get_data",
    ("GET", "/vv"): "get_vv",
    ("GET", "/gossip"): "get_gossip",
    ("GET", "/ks/gossip"): "get_ks_gossip",
    ("GET", "/ks/data"): "get_ks_data",
}


def _make_handler(cluster: LocalCluster, idx: int, admin=None):
    class Handler(BaseHTTPRequestHandler):
        # resolve at request time: a node may be replaced in the cluster
        # (crash + checkpoint-restore) and the port must follow it
        @property
        def node(self):
            return cluster.nodes[idx]
        def log_message(self, *args):  # quiet (gin's request log equivalent off)
            pass

        def _send(self, code: int, body: str, ctype: str = "text/plain"):
            self._send_bytes(code, body.encode(), ctype)

        def _send_bytes(self, code: int, data: bytes, ctype: str,
                        extra_headers=None):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            for k, v in (extra_headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        @property
        def set_node(self):
            if admin is not None:
                return getattr(admin, "set_node", None)
            # demo mode: LocalCluster carries set siblings per replica
            nodes = getattr(cluster, "set_nodes", None)
            return nodes[idx] if nodes else None

        @property
        def seq_node(self):
            if admin is not None:
                return getattr(admin, "seq_node", None)
            nodes = getattr(cluster, "seq_nodes", None)
            return nodes[idx] if nodes else None

        @property
        def map_node(self):
            if admin is not None:
                return getattr(admin, "map_node", None)
            nodes = getattr(cluster, "map_nodes", None)
            return nodes[idx] if nodes else None

        @property
        def composite_node(self):
            if admin is not None:
                return getattr(admin, "composite_node", None)
            nodes = getattr(cluster, "composite_nodes", None)
            return nodes[idx] if nodes else None

        @property
        def ingest(self):
            """The node's ingest front door (crdt_tpu.ingest), or None —
            routes fall back to the direct write paths so a bare
            LocalCluster without front doors keeps serving."""
            if admin is not None:
                return getattr(admin, "ingest", None)
            doors = getattr(cluster, "ingests", None)
            return doors[idx] if doors else None

        @property
        def keyspace(self):
            """The node's ShardedKeyspace (crdt_tpu.keyspace), or None —
            /ks/* routes 404 without one."""
            return getattr(admin, "keyspace", None) \
                if admin is not None else None

        @property
        def ks_door(self):
            """The keyspace front door (tenant-aware admission), or
            None."""
            return getattr(admin, "ks_door", None) \
                if admin is not None else None

        @property
        def consistency(self):
            """The node's ConsistencyPlane (crdt_tpu.consistency), or
            None — /read and /cas 404 without one (a bare LocalCluster
            has no RemotePeers to run quorum rounds over)."""
            return getattr(admin, "consistency", None) \
                if admin is not None else None

        @property
        def leases(self):
            """The node's LeaseManager (crdt_tpu.consistency.leases),
            or None — /lease/grant 404s and /push skips fence checks
            without one."""
            return getattr(admin, "leases", None) \
                if admin is not None else None

        def _send_unavailable(self, exc: ConsistencyUnavailable):
            """503 Service Unavailable + Retry-After: the loud face of
            a strong operation that cannot meet its guarantee — never a
            silently stale value (paired 1:1 with a
            consistency_unavailable event by the plane).  The advisory
            Retry-After mirrors the ingest door's 429s; the body
            carries every field a forwarding origin needs to RE-RAISE
            the refusal without re-counting it."""
            body = {
                "error": "consistency_unavailable",
                "reason": exc.reason, "level": exc.level,
                "op": exc.op, "acks": exc.acks, "quorum": exc.quorum,
                "indeterminate": exc.indeterminate,
                "retry_after_s": exc.retry_after_s,
            }
            if exc.token:
                # the minted-but-unacked op identity: a forwarding
                # origin (and the nemesis prefix oracle) must know WHICH
                # write is outstanding, and under whose rid it minted
                body["token"] = {str(r): s for r, s in exc.token.items()}
            self._send_bytes(
                503,
                json.dumps(body).encode(),
                "application/json",
                extra_headers={
                    "Retry-After": f"{exc.retry_after_s:.3f}"},
            )

        def _send_shed(self, exc: ShedError):
            """429 Too Many Requests + Retry-After: the loud, explicit
            face of the shed policy (never a silent drop).  A tenant
            quota-slice shed names the tenant so a multi-tenant client
            can tell ITS throttle from global backpressure."""
            body = {
                "shed": True, "lane": exc.lane, "n_ops": exc.n_ops,
                "retry_after": exc.retry_after_s,
            }
            if exc.tenant is not None:
                body["tenant"] = exc.tenant
            self._send_bytes(
                429, json.dumps(body).encode(), "application/json",
                extra_headers={"Retry-After": f"{exc.retry_after_s:.3f}"},
            )

        def _parse_vv_query(self, url):
            """?vv=<json {rid: seq}> -> dict, None (absent), or the string
            "bad" (unparseable — caller 400s)."""
            q = parse_qs(url.query)
            if "vv" not in q:
                return None
            try:
                return {
                    int(r): int(s)
                    for r, s in json.loads(q["vv"][0]).items()
                }
            except (ValueError, TypeError, AttributeError):
                return "bad"  # unparseable JSON / non-dict / non-int fields

        def do_GET(self):
            with self._route_span("GET"):
                self._get()

        def do_POST(self):
            with self._route_span("POST"):
                self._post()

        def _route_span(self, method: str):
            """The ``crdt.http.<route>`` span over one whole handler;
            ``#trace=<id>`` names the gossip round of a traced request."""
            route = _ROUTES.get((method, self.path.partition("?")[0]),
                                "other")
            return region(f"crdt.http.{route}",
                          self.headers.get(TRACE_HEADER))

        def _get(self):
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            if parts and parts[0] == "set" and self.set_node is not None:
                sn = self.set_node
                if url.path == "/set":
                    members = sn.members()
                    if members is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"members": members}),
                                   "application/json")
                elif url.path == "/set/gossip":
                    since = self._parse_vv_query(url)
                    if since == "bad":
                        self._send(400, "invalid vv")
                        return
                    payload = sn.gossip_payload(since=since)
                    if payload is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(payload),
                                   "application/json")
                elif url.path == "/set/vv":
                    if not sn.alive:
                        self._send(502, "Unreachable")
                        return
                    vv, floor = sn.vv_snapshot()
                    self._send(200, json.dumps({
                        "vv": {str(r): s for r, s in vv.items()},
                        "floor": {str(r): s for r, s in floor.items()},
                    }), "application/json")
                else:
                    self._send(404, "not found")
                return
            if parts and parts[0] == "seq" and self.seq_node is not None:
                qn = self.seq_node
                if url.path == "/seq":
                    items = qn.items()
                    if items is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"items": items}),
                                   "application/json")
                elif url.path == "/seq/gossip":
                    since = self._parse_vv_query(url)
                    if since == "bad":
                        self._send(400, "invalid vv")
                        return
                    payload = qn.gossip_payload(since=since)
                    if payload is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(payload),
                                   "application/json")
                elif url.path == "/seq/vv":
                    if not qn.alive:
                        self._send(502, "Unreachable")
                        return
                    vv, floor = qn.vv_snapshot()
                    self._send(200, json.dumps({
                        "vv": {str(r): s for r, s in vv.items()},
                        "floor": {str(r): s for r, s in floor.items()},
                    }), "application/json")
                else:
                    self._send(404, "not found")
                return
            if parts and parts[0] == "map" and self.map_node is not None:
                mn = self.map_node
                if url.path == "/map":
                    items = mn.items()
                    if items is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"items": items}),
                                   "application/json")
                elif url.path == "/map/gossip":
                    since = self._parse_vv_query(url)
                    if since == "bad":
                        self._send(400, "invalid vv")
                        return
                    payload = mn.gossip_payload(since=since)
                    if payload is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(payload),
                                   "application/json")
                elif url.path == "/map/vv":
                    if not mn.alive:
                        self._send(502, "Unreachable")
                        return
                    vv, epochs = mn.vv_snapshot()
                    self._send(200, json.dumps({
                        "vv": {str(r): s for r, s in vv.items()},
                        "epochs": epochs,
                        "records": mn.n_records(),
                    }), "application/json")
                else:
                    self._send(404, "not found")
                return
            if parts and parts[0] == "composite" \
                    and self.composite_node is not None:
                cn = self.composite_node
                if url.path == "/composite":
                    items = cn.items()
                    if items is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"items": items}),
                                   "application/json")
                elif url.path == "/composite/gossip":
                    # state-based: the full trimmed dump, no vv query
                    payload = cn.gossip_payload()
                    if payload is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(payload),
                                   "application/json")
                else:
                    self._send(404, "not found")
                return
            if parts and parts[0] == "ks" and self.keyspace is not None:
                ks = self.keyspace
                if url.path == "/ks/gossip":
                    if not self.node.alive:
                        self._send(502, "Unreachable")
                        return
                    q = parse_qs(url.query)
                    try:
                        shard = int(q.get("shard", [None])[0])
                        assert 0 <= shard < ks.n_shards
                    except (TypeError, ValueError, AssertionError):
                        self._send(400, "invalid shard")
                        return
                    # reshard epoch fence: a puller at another epoch gets
                    # a 409 naming ours (its (rid, seq) identities belong
                    # to a different plane generation).  No ?epoch= means
                    # epoch 0 — back-compatible until the first reshard.
                    fence = ks.check_epoch(
                        q.get("epoch", [None])[0], "ks_gossip",
                        peer=self.client_address[0])
                    if fence is not None:
                        self._send(409, json.dumps(fence),
                                   "application/json")
                        return
                    since = self._parse_vv_query(url)
                    if since == "bad":
                        self._send(400, "invalid vv")
                        return
                    trace = self.headers.get(TRACE_HEADER)
                    payload = ks.gossip_payload(shard, since=since)
                    if trace:
                        # serve side of a shard round: same trace id as
                        # the puller's ks_pull_* events (the host plane's
                        # gossip_serve pattern, gone shard-scoped)
                        self.node.events.emit(
                            "ks_gossip_serve", trace=trace, shard=shard,
                            peer=self.client_address[0],
                            delta=since is not None,
                        )
                    # the shard's stability summary rides the BODY: a
                    # round pulls several shards and the header slot
                    # holds only one summary (net.RemotePeer).  The
                    # audit digest (clamped at the same frontier) rides
                    # beside it — zero extra round trips
                    vv, frontier, dig = ks.audit_snapshot(shard)
                    body = {
                        "payload": payload,
                        "vv": {str(r): s for r, s in vv.items()},
                        "frontier": {str(r): s
                                     for r, s in frontier.items()},
                    }
                    if dig is not None:
                        body["digest"] = dig
                    self._send_bytes(200, json.dumps(body).encode(),
                                     "application/json",
                                     extra_headers={TRACE_HEADER: trace}
                                     if trace else None)
                elif url.path == "/ks/data":
                    if not self.node.alive:
                        self._send(502, "Unreachable")
                        return
                    q = parse_qs(url.query)
                    tenant = q.get("tenant", [None])[0]
                    if tenant is not None:
                        self._send(200, json.dumps(
                            {"tenant": tenant,
                             "state": ks.tenant_state(tenant)}
                        ), "application/json")
                    else:
                        self._send(200, json.dumps(
                            {"shards": ks.shard_stats()}
                        ), "application/json")
                else:
                    self._send(404, "not found")
                return
            if url.path == "/metrics":
                # Prometheus text exposition: the node's whole registry +
                # the lattice health gauges, sampled at scrape time (the
                # gauges are always scrape-fresh; an idle node pays zero)
                body = health.render_node_metrics(
                    self.node, set_node=self.set_node,
                    seq_node=self.seq_node, map_node=self.map_node,
                    composite_node=self.composite_node,
                    agent=getattr(admin, "agent", None),
                    ingest=self.ingest,
                    stability=getattr(getattr(admin, "agent", None),
                                      "stability", None),
                    keyspace=self.keyspace,
                    ks_door=self.ks_door,
                    leases=self.leases,
                    watchdog=getattr(getattr(admin, "agent", None),
                                     "watchdog", None),
                )
                self._send(200, body, PROM_CTYPE)
            elif url.path == "/fleet":
                # fleet SLO rollup: this node's exposition + every
                # reachable peer's /metrics, folded by obs.fleet (the
                # same code path as `python -m crdt_tpu.obs fleet`).
                # slo_breach events land in THIS node's black box.
                from crdt_tpu.obs import fleet as fleet_lib

                own = health.render_node_metrics(
                    self.node, set_node=self.set_node,
                    seq_node=self.seq_node, map_node=self.map_node,
                    composite_node=self.composite_node,
                    agent=getattr(admin, "agent", None),
                    ingest=self.ingest,
                    stability=getattr(getattr(admin, "agent", None),
                                      "stability", None),
                    keyspace=self.keyspace,
                    ks_door=self.ks_door,
                    leases=self.leases,
                    watchdog=getattr(getattr(admin, "agent", None),
                                     "watchdog", None),
                )
                texts = {str(self.node.rid): own}
                agent = getattr(admin, "agent", None)
                if agent is not None:
                    for p in agent.peers:
                        if p.backed_off():
                            continue
                        text = p.metrics_text()
                        if text is not None:
                            texts[p.url] = text
                q = parse_qs(url.query)
                slo = {}
                for key in ("admit_p99_ms", "prop_p99_steps",
                            "shed_ratio"):
                    if key in q:
                        try:
                            slo[key] = float(q[key][0])
                        except ValueError:
                            self._send(400, f"invalid {key}")
                            return
                report = fleet_lib.fleet_from_texts(
                    texts, slo=slo or None, events=self.node.events)
                self._send(200, json.dumps(report), "application/json")
            elif url.path == "/audit":
                # divergence audit report (crdt_tpu.obs.audit): watchdog
                # state, per-plane frontier-anchored digests, recorded
                # divergences — the `python -m crdt_tpu.obs audit` feed
                wd = getattr(getattr(admin, "agent", None),
                             "watchdog", None)
                if wd is None:
                    self._send(404, "no audit watchdog on this node")
                else:
                    self._send_bytes(200, wd.report_json(),
                                     "application/json")
            elif url.path == "/ping":
                if self.node.ping():
                    self._send(200, "Pong")
                else:
                    self._send(502, "Unreachable")
            elif url.path == "/data":
                tenant = self.headers.get(TENANT_HEADER)
                if tenant is not None and self.keyspace is not None:
                    # tenant-scoped read: the tenant's slice of the
                    # keyspace, un-qualified (mirror of the write route)
                    if not self.node.alive:
                        self._send(502, "Unreachable")
                        return
                    self._send(200,
                               json.dumps(self.keyspace.tenant_state(tenant)),
                               "application/json")
                    return
                state = self.node.get_state()
                if state is None:
                    self._send(502, "Unreachable")
                else:
                    self._send(200, json.dumps(state), "application/json")
            elif url.path == "/gossip":
                # ?vv=<json {rid: seq}>: delta gossip — only ops the
                # requester is missing.  Plain GET /gossip is the
                # reference's full-log dump (main.go:159) as long as the
                # node has never compacted; after a fold it carries the
                # reserved summary sections a Go peer cannot parse
                since = None
                q = parse_qs(url.query)
                if "vv" in q:
                    try:
                        since = {
                            int(r): int(s)
                            for r, s in json.loads(q["vv"][0]).items()
                        }
                    except Exception:
                        self._send(400, "invalid vv")
                        return
                trace = self.headers.get(TRACE_HEADER)
                body = self.node.gossip_payload_json(since=since)
                if body is None:
                    self._send(502, "Unreachable")
                    return
                if trace:
                    # the serve side of the round: same trace ID as the
                    # puller's pull_* events — grep one ID, see both ends
                    self.node.events.emit(
                        "gossip_serve", trace=trace,
                        peer=self.client_address[0], delta=since is not None,
                        bytes=len(body),
                    )
                # every gossip response piggybacks this node's stability
                # summary — the zero-round-trip feed of the fleet-wide
                # stable frontier (crdt_tpu.consistency.stability) — and,
                # when the audit plane is on, the digest clamped at the
                # SAME frontier (one atomic snapshot: obs.audit needs the
                # digest and frontier to travel as a pair)
                vv, frontier, dig = self.node.audit_snapshot()
                extra = {STABILITY_HEADER:
                         encode_summary(self.node.rid, vv, frontier,
                                        digest=dig)}
                if trace:
                    extra[TRACE_HEADER] = trace
                self._send_bytes(200, body, "application/json",
                                 extra_headers=extra)
            elif url.path == "/read":
                plane = self.consistency
                if plane is None:
                    self._send(404, "no consistency plane on this node")
                    return
                q = parse_qs(url.query)
                key = q.get("key", [None])[0]
                if key is None:
                    self._send(400, "missing key")
                    return
                level = q.get("level", ["eventual"])[0]
                token = decode_token(self.headers.get(SESSION_TOKEN_HEADER))
                if level == "session" and token is None:
                    self._send(400, "session read requires a valid "
                                    f"{SESSION_TOKEN_HEADER} header")
                    return
                staleness = None
                if "staleness" in q:
                    try:
                        staleness = int(q["staleness"][0])
                    except ValueError:
                        self._send(400, "staleness must be an integer "
                                        "op budget")
                        return
                try:
                    value = plane.read(key, level=level, token=token,
                                       staleness=staleness)
                except ValueError as e:
                    self._send(400, str(e))
                    return
                except ConsistencyUnavailable as e:
                    self._send_unavailable(e)
                    return
                self._send(200, json.dumps(
                    {"key": key, "value": value, "level": level}
                ), "application/json")
            elif url.path == "/vv":
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                vv, frontier = self.node.vv_snapshot()  # one lock: consistent pair
                body = {
                    "vv": {str(r): s for r, s in vv.items()},
                    "frontier": {str(r): s for r, s in frontier.items()},
                }
                self._send(200, json.dumps(body), "application/json")
            elif parts and parts[0] == "condition":
                flag = None
                if len(parts) > 1:
                    flag = parts[1]
                else:
                    q = parse_qs(url.query)
                    flag = q.get("alive_status", [None])[0]
                if flag is None or flag.lower() not in ("true", "false", "1", "0"):
                    self._send(500, "invalid alive_status")
                    return
                self.node.set_alive(flag.lower() in ("true", "1"))
                self._send(200, "OK")
            else:
                self._send(404, "not found")

        def _post(self):
            path = urlparse(self.path).path
            if path == "/ingest/page":
                front = self.ingest
                if front is None:
                    self._send(404, "no ingest front door on this node")
                    return
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n)
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                tenant = self.headers.get(TENANT_HEADER)
                try:
                    if tenant is not None and self.ks_door is not None:
                        # reshard epoch fence on the page-admit surface:
                        # a writer that STAMPS its epoch (the header is
                        # optional — un-stamped writers predate the
                        # fence and route by the live map either way)
                        # gets a 409 naming ours when stale, so a
                        # mid-reshard client learns the map moved
                        # instead of silently writing against it
                        eh = self.headers.get(KS_EPOCH_HEADER)
                        if eh is not None:
                            fence = self.keyspace.check_epoch(
                                eh, "ingest_page",
                                peer=self.client_address[0])
                            if fence is not None:
                                self._send(409, json.dumps(fence),
                                           "application/json")
                                return
                        # tenant-scoped page: rendezvous fan-out across
                        # shard lanes, per-tenant quota, whole-page shed
                        out = self.ks_door.admit_page(raw, tenant)
                    else:
                        out = front.admit_page(raw, tenant=tenant)
                except PageFormatError as e:
                    # decode-validates-everything: the page is quarantined
                    # whole (counted + black-boxed inside admit_page); a
                    # truncated page is ALWAYS "no page", never "some ops"
                    self._send(400, f"page quarantined: {e}")
                    return
                except ValueError as e:  # bad tenant name
                    self._send(400, str(e))
                    return
                except ShedError as e:
                    self._send_shed(e)
                    return
                self._send(200, json.dumps(out), "application/json")
                return
            if path.startswith("/admin/") and admin is not None:
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    self._send(400, "invalid body")
                    return
                try:
                    if path == "/admin/pull":
                        ok = admin.admin_pull(body.get("peer"))
                        self._send(200, json.dumps({"pulled": bool(ok)}),
                                   "application/json")
                    elif path == "/admin/barrier":
                        frontier = admin.admin_barrier()
                        self._send(
                            200,
                            json.dumps({
                                "frontier": {str(r): s
                                             for r, s in frontier.items()}
                            }),
                            "application/json",
                        )
                    elif path == "/admin/stability_gc":
                        frontier = admin.admin_stability_gc()
                        self._send(
                            200,
                            json.dumps({
                                "frontier": {str(r): s
                                             for r, s in frontier.items()}
                            }),
                            "application/json",
                        )
                    elif path == "/admin/checkpoint":
                        snap = admin.checkpoint_now()
                        if snap is None:
                            self._send(400, "no checkpoint dir configured")
                        else:
                            self._send(200, json.dumps({"snapshot": snap}),
                                       "application/json")
                    elif path == "/admin/set_pull":
                        ok = admin.admin_set_pull(body.get("peer"))
                        self._send(200, json.dumps({"pulled": bool(ok)}),
                                   "application/json")
                    elif path == "/admin/set_barrier":
                        floor = admin.admin_set_barrier()
                        self._send(
                            200,
                            json.dumps({
                                "floor": {str(r): s
                                          for r, s in floor.items()}
                            }),
                            "application/json",
                        )
                    elif path == "/admin/seq_pull":
                        ok = admin.admin_seq_pull(body.get("peer"))
                        self._send(200, json.dumps({"pulled": bool(ok)}),
                                   "application/json")
                    elif path == "/admin/map_pull":
                        ok = admin.admin_map_pull(body.get("peer"))
                        self._send(200, json.dumps({"pulled": bool(ok)}),
                                   "application/json")
                    elif path == "/admin/map_barrier":
                        out = admin.admin_map_barrier()
                        self._send(
                            200,
                            json.dumps({
                                "epochs": {
                                    str(k): int(e)
                                    for k, e in out["epochs"].items()
                                },
                                "status": out["status"],
                            }),
                            "application/json",
                        )
                    elif path == "/admin/composite_pull":
                        ok = admin.admin_composite_pull(body.get("peer"))
                        self._send(200, json.dumps({"pulled": bool(ok)}),
                                   "application/json")
                    elif path == "/admin/ks_pull":
                        fresh = admin.admin_ks_pull(body.get("peer"))
                        self._send(200, json.dumps({"fresh": int(fresh)}),
                                   "application/json")
                    elif path == "/admin/ks_reshard":
                        try:
                            out = admin.admin_ks_reshard(body)
                        except ValueError as e:
                            self._send(400, str(e))
                        else:
                            self._send(200, json.dumps(out),
                                       "application/json")
                    elif path == "/admin/ks_gc":
                        folded = admin.admin_ks_gc()
                        self._send(
                            200,
                            json.dumps({
                                "shards": {
                                    str(i): {str(r): s
                                             for r, s in f.items()}
                                    for i, f in folded.items()
                                }
                            }),
                            "application/json",
                        )
                    elif path == "/admin/seq_barrier":
                        floor = admin.admin_seq_barrier()
                        self._send(
                            200,
                            json.dumps({
                                "floor": {str(r): s
                                          for r, s in floor.items()}
                            }),
                            "application/json",
                        )
                    else:
                        self._send(404, "not found")
                except Exception as e:  # surfaced to the driving test: a
                    # failing pull/barrier is an invariant violation (I4),
                    # never a silent skip (the reference's quirk 0.1.8)
                    self._send(500, f"{type(e).__name__}: {e}")
                return
            if path.startswith("/set/") and self.set_node is not None:
                sn = self.set_node
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    assert isinstance(body, dict)
                except Exception:
                    self._send(400, "invalid body")
                    return
                if path == "/set/add":
                    ident = sn.add(str(body.get("elem", "")))
                    if ident is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(
                            {"rid": ident[0], "seq": ident[1]}
                        ), "application/json")
                elif path == "/set/remove":
                    if not sn.alive:
                        self._send(502, "Unreachable")
                        return
                    ident = sn.remove(str(body.get("elem", "")))
                    op = sn.op_record(ident) if ident else None
                    self._send(200, json.dumps({
                        "removed": ident is not None,
                        "rid": ident[0] if ident else None,
                        "seq": ident[1] if ident else None,
                        "tags": (op or {}).get("tags", []),
                    }), "application/json")
                elif path == "/set/collect":
                    if not sn.alive:
                        self._send(502, "Unreachable")
                        return
                    try:
                        floor = {
                            int(r): int(s)
                            for r, s in (body.get("floor") or {}).items()
                        }
                    except Exception:
                        self._send(400, "invalid floor")
                        return
                    sn.collect(floor)
                    self._send(200, "OK")
                else:
                    self._send(404, "not found")
                return
            if path.startswith("/seq/") and self.seq_node is not None:
                qn = self.seq_node
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    assert isinstance(body, dict)
                except Exception:
                    self._send(400, "invalid body")
                    return
                if path == "/seq/insert":
                    idx = body.get("index")
                    try:
                        idx = None if idx is None else int(idx)
                    except (TypeError, ValueError):
                        self._send(400, "invalid index")
                        return
                    ident = qn.insert_at(idx, str(body.get("elem", "")))
                    if ident is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps(
                            {"rid": ident[0], "seq": ident[1]}
                        ), "application/json")
                elif path == "/seq/remove":
                    if not qn.alive:
                        self._send(502, "Unreachable")
                        return
                    try:
                        idx = int(body.get("index"))
                    except (TypeError, ValueError):
                        self._send(400, "invalid index")
                        return
                    ident = qn.remove_at(idx)
                    op = qn.op_record(ident) if ident else None
                    self._send(200, json.dumps({
                        "removed": ident is not None,
                        "rid": ident[0] if ident else None,
                        "seq": ident[1] if ident else None,
                        "target": (op or {}).get("del"),
                    }), "application/json")
                elif path == "/seq/collect":
                    if not qn.alive:
                        self._send(502, "Unreachable")
                        return
                    try:
                        floor = {
                            int(r): int(s)
                            for r, s in (body.get("floor") or {}).items()
                        }
                    except Exception:
                        self._send(400, "invalid floor")
                        return
                    qn.collect(floor)
                    self._send(200, "OK")
                else:
                    self._send(404, "not found")
                return
            if path.startswith("/map/") and self.map_node is not None:
                mn = self.map_node
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    assert isinstance(body, dict)
                except Exception:
                    self._send(400, "invalid body")
                    return
                if path == "/map/upd":
                    try:
                        delta = int(body.get("delta"))
                    except (TypeError, ValueError):
                        self._send(400, "invalid delta")
                        return
                    front = self.ingest
                    tenant = self.headers.get(TENANT_HEADER)
                    if tenant is not None and self.ks_door is not None:
                        # tenant-scoped map write: books against the
                        # tenant's quota slice, key lands qualified
                        try:
                            ident = self.ks_door.admit_map_upd(
                                tenant, str(body.get("key", "")), delta)
                        except ShedError as e:
                            self._send_shed(e)
                            return
                        except ValueError as e:  # bad tenant name
                            self._send(400, str(e))
                            return
                    elif front is not None and front.map is not None:
                        # singleton writes share the page path's admission
                        # queue: one drain = one batched mint (parity with
                        # the direct path pinned in tests/test_ingest.py)
                        try:
                            ident = front.admit_map_upd(
                                str(body.get("key", "")), delta)
                        except ShedError as e:
                            self._send_shed(e)
                            return
                    else:
                        ident = mn.upd(str(body.get("key", "")), delta)
                    if ident is None:
                        self._send(502, "Unreachable")
                    else:
                        op = mn.op_record(ident) or {}
                        self._send(200, json.dumps(
                            {"rid": ident[0], "seq": ident[1],
                             "e": int(op.get("e", 0))}
                        ), "application/json")
                elif path == "/map/rem":
                    if not mn.alive:
                        self._send(502, "Unreachable")
                        return
                    ident = mn.rem(str(body.get("key", "")))
                    op = mn.op_record(ident) if ident else None
                    self._send(200, json.dumps({
                        "removed": ident is not None,
                        "rid": ident[0] if ident else None,
                        "seq": ident[1] if ident else None,
                        "obs": (op or {}).get("obs", {}),
                        "e": int((op or {}).get("e", 0)),
                    }), "application/json")
                elif path == "/map/reset":
                    if not mn.alive:
                        self._send(502, "Unreachable")
                        return
                    try:
                        epochs = {
                            str(k): int(e)
                            for k, e in (body.get("epochs") or {}).items()
                        }
                    except Exception:
                        self._send(400, "invalid epochs")
                        return
                    mn.adopt_epochs(epochs)
                    self._send(200, "OK")
                else:
                    self._send(404, "not found")
                return
            if path.startswith("/composite/") \
                    and self.composite_node is not None:
                cn = self.composite_node
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    assert isinstance(body, dict)
                except Exception:
                    self._send(400, "invalid body")
                    return
                if path == "/composite/upd":
                    try:
                        delta = int(body.get("delta"))
                    except (TypeError, ValueError):
                        self._send(400, "invalid delta")
                        return
                    front = self.ingest
                    if front is not None and front.composite is not None:
                        try:
                            value = front.admit_composite_upd(
                                str(body.get("key", "")), delta)
                        except ShedError as e:
                            self._send_shed(e)
                            return
                    else:
                        value = cn.upd(str(body.get("key", "")), delta)
                    if value is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"value": value}),
                                   "application/json")
                elif path == "/composite/rem":
                    removed = cn.rem(str(body.get("key", "")))
                    if removed is None:
                        self._send(502, "Unreachable")
                    else:
                        self._send(200, json.dumps({"removed": removed}),
                                   "application/json")
                else:
                    self._send(404, "not found")
                return
            if path == "/ks/compact":
                ks = self.keyspace
                if ks is None:
                    self._send(404, "no keyspace tier on this node")
                    return
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    shard = int(body.get("shard"))
                    assert 0 <= shard < ks.n_shards
                    frontier = {
                        int(r): int(s)
                        for r, s in (body.get("frontier") or {}).items()
                    }
                except Exception:
                    self._send(400, "invalid shard/frontier")
                    return
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                # reshard epoch fence: a frontier minted against another
                # plane generation must never fold this one (the (rid,
                # seq) spaces were reborn at cutover).  Absent epoch =
                # epoch 0, back-compatible until the first reshard.
                fence = ks.check_epoch(body.get("epoch"), "ks_compact",
                                       peer=self.client_address[0])
                if fence is not None:
                    self._send(409, json.dumps(fence), "application/json")
                    return
                ks.compact_shard(shard, frontier)
                self._send(200, "OK")
                return
            if path == "/ks/migrate":
                ks = self.keyspace
                if ks is None:
                    self._send(404, "no keyspace tier on this node")
                    return
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    shard = int(body.get("shard"))
                    payload = body.get("payload")
                    assert isinstance(payload, dict)
                except Exception:
                    self._send(400, "invalid shard/payload")
                    return
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                # epoch match AND this node must be IN its own MIGRATE
                # window — both refusals use the same 409 grammar naming
                # the live epoch, so the sender knows to retry later
                # (peer not told yet) or stand down (already cut over)
                fence = ks.check_epoch(body.get("epoch"), "ks_migrate",
                                       peer=self.client_address[0])
                if fence is not None:
                    self._send(409, json.dumps(fence), "application/json")
                    return
                out = ks.reshard.receive_migration(
                    shard, payload, peer=self.client_address[0])
                if out.get("ok"):
                    self._send(200, json.dumps(out), "application/json")
                elif "quarantined" in out:
                    self._send(400, json.dumps(out), "application/json")
                else:
                    # not in a MIGRATE window at this epoch: same 409
                    # grammar as the fence (the sender retries later)
                    out["fenced"] = True
                    out["epoch"] = ks.epoch
                    self._send(409, json.dumps(out), "application/json")
                return
            if path == "/compact":
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    frontier = {
                        int(r): int(s)
                        for r, s in (body.get("frontier") or {}).items()
                    }
                except Exception:
                    self._send(400, "invalid frontier")
                    return
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                self.node.compact(frontier)
                self._send(200, "OK")
                return
            if path == "/push":
                # the synchronous write-quorum leg of CAS (crdt_tpu
                # .consistency.plane): merge the pushed payload BEFORE
                # answering, so a 200 proves this node's vv dominates
                # every op it carried
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    payload = body.get("payload")
                    assert isinstance(payload, dict)
                    fences = {int(s): int(f)
                              for s, f in (body.get("fences") or {}).items()}
                    trace = body.get("trace")
                    trace = None if trace is None else str(trace)
                except Exception:
                    self._send(400, "invalid payload")
                    return
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                if fences and self.leases is not None:
                    # fence firewall BEFORE the merge: a push stamped
                    # with a superseded lease epoch is refused WHOLE —
                    # the zombie-coordinator commit path ends here.  The
                    # coordinator's CAS trace rode the body, so a reject
                    # (and the merge's op_visible below) joins its trace.
                    stale = self.leases.check_push_fences(fences,
                                                          trace=trace)
                    if stale is not None:
                        self._send_bytes(
                            409,
                            json.dumps({"fenced": True,
                                        "slot": stale["slot"],
                                        "fence": stale["fence"]}).encode(),
                            "application/json")
                        return
                try:
                    if trace:
                        with span("crdt.push", trace):
                            fresh = self.node.receive(payload)
                    else:
                        fresh = self.node.receive(payload)
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, f"malformed payload: "
                                    f"{type(e).__name__}: {e}")
                    return
                self._send(200, json.dumps({"fresh": fresh}),
                           "application/json")
                return
            if path == "/lease/grant":
                leases = self.leases
                if leases is None:
                    self._send(404, "no lease manager on this node")
                    return
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    slot = int(body["slot"])
                    holder = str(body["holder"])
                    fence = int(body["fence"])
                    ttl = float(body["ttl"])
                    assert 0 <= slot < leases.n_slots and fence > 0 \
                        and ttl > 0
                except Exception:
                    self._send(400, "invalid grant request: need "
                                    "slot/holder/fence/ttl")
                    return
                if not self.node.alive:
                    self._send(502, "Unreachable")
                    return
                self._send(200, json.dumps(
                    leases.grant(slot, holder, fence, ttl)
                ), "application/json")
                return
            if path == "/cas":
                plane = self.consistency
                if plane is None:
                    self._send(404, "no consistency plane on this node")
                    return
                n = int(self.headers.get("Content-Length", 0))
                try:
                    body = json.loads(self.rfile.read(n) or b"{}")
                    assert isinstance(body, dict)
                    if "ops" in body:
                        # multi-key batch: {"ops": {key: {"expect",
                        # "update"}}} — every pair checked under one
                        # view, applied all-or-nothing
                        assert isinstance(body["ops"], dict) and body["ops"]
                        ops = {}
                        for k, ou in body["ops"].items():
                            e = ou.get("expect")
                            ops[str(k)] = (None if e is None else str(e),
                                           str(ou["update"]))
                    else:
                        key = str(body["key"])
                        expect = body.get("expect")
                        expect = None if expect is None else str(expect)
                        ops = {key: (expect, str(body["update"]))}
                    hops = int(body.get("hops", 0))
                    timeout = body.get("timeout")
                    timeout = None if timeout is None else float(timeout)
                    assert hops >= 0
                except Exception:
                    self._send(400, "invalid body: need key/update or "
                                    "ops={key:{expect,update}} "
                                    "(expect null = key must be absent)")
                    return
                # the request's causal thread: header from external
                # clients, body field across coordinator forwarding hops
                # (the plane puts it there) — header wins when both ride
                trace = self.headers.get(TRACE_HEADER) \
                    or body.get("trace")
                trace = None if trace is None else str(trace)
                try:
                    token = plane.cas_multi(ops, timeout=timeout,
                                            hops=hops, trace=trace)
                except CasConflict as e:
                    self._send_bytes(
                        409,
                        json.dumps({
                            "conflict": True, "key": e.key,
                            "expect": e.expect, "actual": e.actual,
                            "coordinator": e.coordinator,
                            "fence": e.fence,
                        }).encode(),
                        "application/json",
                    )
                    return
                except ConsistencyUnavailable as e:
                    self._send_unavailable(e)
                    return
                self._send_bytes(
                    200,
                    json.dumps({"token": {str(r): s
                                          for r, s in token.items()}}
                               ).encode(),
                    "application/json",
                    extra_headers={
                        SESSION_TOKEN_HEADER: encode_token(token)},
                )
                return
            if path != "/data":
                self._send(404, "not found")
                return
            n = int(self.headers.get("Content-Length", 0))
            try:
                cmd = json.loads(self.rfile.read(n) or b"{}")
                assert isinstance(cmd, dict)
                cmd = {str(k): str(v) for k, v in cmd.items()}
            except Exception:
                self._send(500, "Request body is invalid")  # main.go:179-186
                return
            tenant = self.headers.get(TENANT_HEADER)
            if tenant is not None and self.ks_door is not None:
                # tenant-scoped write: every pair routes to its owning
                # shard's lane (all-or-nothing vs the shed policy); the
                # LAST pair's ident mints the session token, exactly as
                # the single-plane path does for its one ident
                try:
                    idents = self.ks_door.admit_cmd(tenant, cmd)
                except ShedError as e:
                    self._send_shed(e)
                    return
                except ValueError as e:  # bad tenant name
                    self._send(400, str(e))
                    return
                if idents and all(i is not None for i in idents):
                    ident = idents[-1]
                    self._send_bytes(
                        200, b"Inserted", "text/plain",
                        extra_headers={SESSION_TOKEN_HEADER: encode_token(
                            {ident[0]: ident[1]})},
                    )
                else:
                    self._send(502, "Unreachable")
                return
            front = self.ingest
            if front is not None:
                # the single-op /data route rides the same admission
                # queue as op pages: concurrent posters fuse into one
                # jitted ingest dispatch per drain
                try:
                    ident = front.admit_kv(cmd, tenant=tenant)
                except ShedError as e:
                    self._send_shed(e)
                    return
                if ident is not None:
                    # the ticket ident IS the session token: the vv
                    # watermark a session read must dominate to see this
                    # write.  Rides a response header so the body stays
                    # byte-compatible with the Go surface ("Inserted").
                    self._send_bytes(
                        200, b"Inserted", "text/plain",
                        extra_headers={SESSION_TOKEN_HEADER: encode_token(
                            {ident[0]: ident[1]})},
                    )
                else:
                    self._send(502, "Unreachable")
                return
            if self.node.add_command(cmd):
                self._send(200, "Inserted")  # main.go:208
            else:
                self._send(502, "Unreachable")

    return Handler


class HttpCluster:
    """Serve every node of a LocalCluster on its reference port."""

    def __init__(self, cluster: LocalCluster, host: str = "127.0.0.1"):
        self.cluster = cluster
        self.host = host
        self.servers: List[ThreadingHTTPServer] = []
        self.ports: List[int] = []
        self._threads: List[threading.Thread] = []

    def start(self, ports: Optional[List[int]] = None) -> List[int]:
        ports = ports or [0] * len(self.cluster.nodes)  # 0 = ephemeral
        for idx, port in enumerate(ports[: len(self.cluster.nodes)]):
            srv = ThreadingHTTPServer(
                (self.host, port), _make_handler(self.cluster, idx)
            )
            self.servers.append(srv)
            self.ports.append(srv.server_address[1])
            t = threading.Thread(target=srv.serve_forever, daemon=True)
            t.start()
            self._threads.append(t)
        return self.ports

    def stop(self) -> None:
        for srv in self.servers:
            srv.shutdown()
            srv.server_close()
        for t in self._threads:
            t.join(timeout=5)
        self.servers.clear()
        self._threads.clear()
