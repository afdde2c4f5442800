"""Mesh-sharded anti-entropy tests on the 8-virtual-device CPU mesh
(conftest.py sets xla_force_host_platform_device_count=8): the explicit
collective paths (pmax, recursive-doubling ppermute join) must agree with
the single-device reference implementations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from crdt_tpu.models import gcounter, oplog
from crdt_tpu.parallel import mesh as mesh_lib
from crdt_tpu.parallel import swarm
from tests import helpers
from tests.helpers import tree_equal


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return mesh_lib.make_mesh(8)


def _counter_swarm(rng, r, n_nodes=8):
    counts = np.asarray(rng.integers(0, 100, (r, n_nodes)), np.int32)
    return swarm.make(gcounter.GCounter(counts=jnp.asarray(counts)))


def test_pmax_converge_matches_local(mesh8):
    rng = np.random.default_rng(0)
    s = _counter_swarm(rng, r=64)
    expect = swarm.converge(s, gcounter.join, gcounter.zero(8))

    sharded = mesh_lib.shard_swarm(s, mesh8)
    step = mesh_lib.pmax_converge(mesh8)
    got = step(sharded)
    assert tree_equal(jax.device_get(got.state), jax.device_get(expect.state))


def test_pmax_converge_respects_alive_mask(mesh8):
    rng = np.random.default_rng(1)
    s = _counter_swarm(rng, r=32)
    s = swarm.set_alive(s, 5, False)
    s = swarm.set_alive(s, 17, False)
    expect = swarm.converge(s, gcounter.join, gcounter.zero(8))

    got = mesh_lib.pmax_converge(mesh8)(mesh_lib.shard_swarm(s, mesh8))
    assert tree_equal(jax.device_get(got.state), jax.device_get(expect.state))


def test_sharded_converge_generic_join_oplog(mesh8):
    rng = np.random.default_rng(2)
    r, cap = 16, 64
    logs = helpers.rand_oplog_family(rng, n_logs=r, capacity=cap, pool=30, take=10)
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *logs)
    s = swarm.make(state)
    neutral = oplog.empty(cap)
    expect = swarm.converge(s, jax.vmap(oplog.merge), neutral)

    step = mesh_lib.sharded_converge(
        mesh8,
        join_batched=jax.vmap(oplog.merge),
        join_single=oplog.merge,
        neutral=neutral,
    )
    got = step(mesh_lib.shard_swarm(s, mesh8))
    assert tree_equal(jax.device_get(got.state), jax.device_get(expect.state))
    # converged log on every replica = union of all ops
    sizes = np.asarray(jax.vmap(oplog.size)(got.state))
    assert (sizes == sizes[0]).all()


@pytest.mark.parametrize("n_dev", [8, 6])
def test_allreduce_join_both_paths(n_dev):
    """n_dev=8 exercises the recursive-doubling ppermute butterfly; n_dev=6
    (non-power-of-two) exercises the all_gather + tree-reduce fallback."""
    rng = np.random.default_rng(3)
    cap = 32
    logs = helpers.rand_oplog_family(rng, n_logs=n_dev, capacity=cap, pool=20, take=8)
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *logs)

    m = mesh_lib.make_mesh(n_dev)
    from jax.sharding import PartitionSpec as P

    def body(x):
        single = jax.tree.map(lambda l: l[0], x)
        out = mesh_lib.allreduce_join(
            oplog.merge, single, "replica", n_dev, neutral=oplog.empty(cap)
        )
        return jax.tree.map(lambda l: l[None], out)

    got = jax.jit(
        shard_map(body, mesh=m, in_specs=P("replica"), out_specs=P("replica"))
    )(state)

    expect = logs[0]
    for l in logs[1:]:
        expect = oplog.merge(expect, l)
    for i in range(n_dev):
        assert tree_equal(jax.tree.map(lambda x, _i=i: x[_i], jax.device_get(got)), jax.device_get(expect))


def test_pjit_auto_sharding_gossip_round(mesh8):
    """The pjit story: jit the plain gossip round over sharded inputs and let
    XLA insert the cross-device gathers — no shard_map needed."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(4)
    s = _counter_swarm(rng, r=64)
    sharded = mesh_lib.shard_swarm(s, mesh8)
    peers = swarm.random_peers(jax.random.key(0), 64)
    peers = jax.device_put(peers, NamedSharding(mesh8, P("replica")))

    step = jax.jit(lambda sw, p: swarm.gossip_round(sw, p, gcounter.join))
    got = step(sharded, peers)
    expect = swarm.gossip_round(s, peers, gcounter.join)
    assert tree_equal(jax.device_get(got.state), jax.device_get(expect.state))
