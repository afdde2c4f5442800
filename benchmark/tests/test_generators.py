"""The generators are deterministic from the seed, change only order and
content (never the amount of work) across seeds, speak the program's
page format, and the copied reference agrees with the program."""
from __future__ import annotations

import json

import numpy as np

from benchmark import oracle, pages, spec
from benchmark.generators import kv_mixed, ycsb_load

BENCH = spec.load()
YCSB = ycsb_load.plan(spec.config(BENCH, "ycsb_ks.r3s4"),
                      spec.traffic("load_sat"), 0)
KV = kv_mixed.plan(spec.config(BENCH, "ref_kv.r5"),
                   spec.traffic("mixed_rate"), 0)
BIG_SEED = 2**31 + 12345


def fnv_reference(v: int) -> int:
    """YCSB Utils.fnvhash64, in Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        v >>= 8
        h = (h * 1099511628211) & ((1 << 64) - 1)
    s = h - (1 << 64) if h >= 1 << 63 else h
    return abs(s) if s != -(1 << 63) else 0


def test_fnvhash64_matches_ycsb():
    nums = [0, 1, 2, 255, 256, 123456789, 1 << 40, (1 << 55) + 7]
    got = ycsb_load.fnvhash64(np.asarray(nums, np.uint64)).tolist()
    assert got == [fnv_reference(n) for n in nums]


def small(plan, **kw):
    return dict(plan, **kw)


def test_ycsb_resident_is_deterministic_and_sized_by_config():
    p = small(YCSB, resident=300, seed=BIG_SEED)
    a, b = ycsb_load.resident(p), ycsb_load.resident(dict(p))
    assert a[1] == b[1] and a[2] == b[2] and (a[0] == b[0]).all()
    c = ycsb_load.resident(dict(p, seed=7))
    assert len(c[1]) == len(a[1]) == 300 * p["fieldcount"]
    assert set(c[1]).isdisjoint(a[1])           # the seed moves the keys
    assert all(len(v) == p["fieldlength"] for v in a[2])
    assert not any(oracle.atoi(v) is not None for v in a[2])  # all LWW


def test_ycsb_pages_belong_to_their_tenant_and_repeat():
    p = small(YCSB, resident=100, seed=BIG_SEED)
    q1 = ycsb_load.TenantQueues(p["seed"], p["resident"], p["tenants"])
    q2 = ycsb_load.TenantQueues(p["seed"], p["resident"], p["tenants"])
    for c in (0, 5):
        k1, v1 = ycsb_load.page_contents(p, q1, c, 3)
        k2, v2 = ycsb_load.page_contents(p, q2, c, 3)
        assert (k1, v1) == (k2, v2)
        assert len(k1) == p["records_per_page"] * p["fieldcount"]
        nums = {int(k.split("/")[0][4:]) for k in k1}
        assert all(n % p["tenants"] == c for n in nums)
    # pages of one client never repeat a record, nor hit the resident set
    keys = [k for pg in range(4) for k in ycsb_load.page_contents(p, q1, 2, pg)[0]]
    assert len(keys) == len(set(keys))
    assert set(keys).isdisjoint(ycsb_load.resident(p)[1])


def test_kv_schedule_same_work_for_every_seed():
    a = kv_mixed.schedule(dict(KV, seed=BIG_SEED), 30.0)
    b = kv_mixed.schedule(dict(KV, seed=BIG_SEED), 30.0)
    c = kv_mixed.schedule(dict(KV, seed=3), 30.0)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert len(a["due"]) == len(c["due"]) == round(KV["rate"] * 30)
    assert not np.array_equal(a["replica"], c["replica"])
    assert np.all(np.diff(a["due"]) >= 0) and 0 <= a["due"][0]
    assert a["due"][-1] < 30.0
    assert a["delta"].min() >= KV["delta_min"] and a["delta"].max() <= KV["delta_max"]


def test_page_encoder_speaks_the_program_format():
    from crdt_tpu.ingest.wire import decode_page

    keys = [f"user{i}/f0" for i in range(5)]
    vals = [f"v{i}x" for i in range(5)]
    page = decode_page(pages.encode(3, 7, 10, keys, vals))
    assert (page.origin, page.page_seq, page.n_ops) == (3, 7, 5)
    assert [cmd for _, cmd in page.rows()] == [{k: v} for k, v in zip(keys, vals)]
    assert all(ts is None for ts, _ in page.rows())


def test_visibility_tracker():
    v = kv_mixed.Visibility(2)
    v.on_vv(0, {0: 5}, 1.0)
    v.on_ack(0, 0, 3, 2.0)        # replica 0 already covers it
    v.on_ack(1, 0, 7, 2.5)
    assert v.visible == {} and v.outstanding() == 3
    v.on_vv(1, {0: 7}, 3.0)
    assert v.visible == {0: 3.0}
    v.on_vv(0, {0: 9}, 4.0)
    assert v.visible == {0: 3.0, 1: 4.0} and v.outstanding() == 0


def test_p95_is_nearest_rank():
    assert kv_mixed.p95(list(range(1, 101))) == 95
    assert kv_mixed.p95([1.0] * 94 + [9.0] * 6) == 9.0


def test_reference_agrees_with_the_program():
    """Two replicas, counters and LWW strings on shared keys, gossip both
    ways: the program's view equals the copied reference's."""
    from crdt_tpu.api.node import ReplicaNode

    rng = np.random.default_rng(5)
    nodes = [ReplicaNode(rid=r, capacity=256) for r in range(2)]
    refs = [oracle.OracleReplica(rid=r) for r in range(2)]
    seqs = [0, 0]                           # a writer's seqs run from 0
    for i in range(120):
        r = int(rng.integers(2))
        key = f"k{rng.integers(6)}"
        val = str(int(rng.integers(-20, -10))) if rng.random() < 0.6 \
            else f"s{rng.integers(1000)}"
        ts = 1000 + i // 2                  # same-ms writes on both replicas
        nodes[r].add_command({key: val}, ts=ts)
        # op identity carries the absolute ms: node epochs differ
        refs[r].add((ts + nodes[r].clock.epoch_ms, r, seqs[r]), {key: val})
        seqs[r] += 1
    for a, b in ((0, 1), (1, 0)):
        nodes[b].receive(nodes[a].gossip_payload())
        refs[b].merge(refs[a])
    want = oracle.converged_state(refs)
    assert nodes[0].get_state() == nodes[1].get_state() == want
    assert refs[0].state() == want


def test_mismatches():
    assert oracle.mismatches({"a": "1"}, {"a": "1"}) == 0
    assert oracle.mismatches({"a": "1", "b": "2"}, {"a": "2", "c": "3"}) == 3
    assert oracle.mismatches({"a": "1"}, {"a": "2"}, ignore=["a"]) == 0
    assert oracle.mismatches(None, {"a": "1"}) == 1


def test_plans_are_json():
    json.dumps(YCSB)
    json.dumps(KV)
