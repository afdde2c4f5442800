"""The correctness check catches a broken timed path.  Each test drives a
whole run on the CPU at a small size (the harness's look for a chip
skipped) with one fault planted underneath the served path, and sees
``correct`` come out false; a sound run and the control (reading before
gossip quiesces, ``--control stale_read``) bracket them."""
from __future__ import annotations

import pytest

from benchmark import run as bench_run

SCALE = {
    "ycsb_ks.load_sat": {
        # the tests see 8 virtual CPU devices (conftest.py), where
        # keyspace_mesh=auto would fold through the mesh engine; the cell
        # has one chip, where auto is the per-lane host path
        ("config", "deployment.cluster.keyspace_mesh"): "off",
        ("config", "records.recordcount"): 512,
        ("config", "deployment.cluster.keyspace_capacity"): 16384,
        ("traffic", "warm_batches"): [256, 512, 1024, 2048, 4096, 8192],
        ("traffic", "quiesce_s"): 8,
        ("traffic", "ramp_s"): 1,
    },
    "ref_kv.mixed_rate": {
        ("config", "deployment.cluster.log_capacity"): 4096,
        ("traffic", "write_rate_per_s"): 60,
        ("traffic", "warm_batches"): [256, 512],
        ("traffic", "drain_s"): 20,
    },
}
CELLS = sorted(SCALE)


def plant(monkeypatch, fault: str) -> None:
    from crdt_tpu.api.node import ReplicaNode
    from crdt_tpu.models import oplog

    if fault == "state_unchanged":
        # the merge returns the log it was given: writes are acknowledged
        # and never land
        monkeypatch.setattr(oplog, "merge_checked_donating",
                            lambda log, batch: (log, oplog.size(log)))
    elif fault == "half_batch":
        orig = ReplicaNode._merge_batch

        def half(self, ops, fresh):
            keep = fresh // 2
            if keep:
                orig(self, {k: v[:keep] for k, v in ops.items()}, keep)
        monkeypatch.setattr(ReplicaNode, "_merge_batch", half)
    elif fault == "no_exchange":
        # the exchange between replicas left out: gossip merges nothing
        monkeypatch.setattr(ReplicaNode, "receive", lambda self, payload: 0)
    elif fault == "one_replica_deaf":
        # one replica takes in nothing from gossip once the window opens
        # (the preload, in set-up, still lands): only that replica's reads
        # can show it
        from benchmark.cluster import Cluster

        deaf = []
        orig_start, orig_recv = Cluster.start_agents, ReplicaNode.receive

        def start(self):
            deaf.append(self.hosts[-1].node.rid)
            orig_start(self)

        def receive(self, payload):
            return 0 if self.rid in deaf else orig_recv(self, payload)
        monkeypatch.setattr(Cluster, "start_agents", start)
        monkeypatch.setattr(ReplicaNode, "receive", receive)
    elif fault == "answer_altered":
        orig_m = oplog.materialize

        def altered(kv, keys, values):
            out = orig_m(kv, keys, values)
            for k in sorted(out)[:1]:
                out[k] = out[k] + "0"
            return out
        monkeypatch.setattr(oplog, "materialize", altered)
    else:
        raise AssertionError(fault)


def one_run(cell: str, seed: int, control: str = ""):
    return bench_run.run(cell, seed, 3.0, False, rehearse=True,
                         control=control, scale=SCALE[cell])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = one_run(cell, 11)
    assert res["correct"] is True, res["checks"]
    assert res["facts"]["window_compiles"] == 0, res["facts"]["window_traced_programs"]
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "one_replica_deaf",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    plant(monkeypatch, fault)
    res = one_run(cell, 12)
    assert res["correct"] is False, (fault, res["checks"])
    if fault == "one_replica_deaf" and cell == "ycsb_ks.load_sat":
        # every tenant is read on every replica: the deaf one shows it
        assert res["facts"]["mismatched_keys_per_replica"][-1] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = one_run(cell, 13, control="stale_read")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["mismatched_keys"]["value"] > 0
