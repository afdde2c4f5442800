"""Device-mesh anti-entropy: the reference's HTTP gossip backend re-expressed
as XLA collectives over ICI/DCN.

The reference's communication backend is pull-based JSON-over-HTTP between
replicas (/root/reference/main.go:226-261).  On a TPU pod the replica axis is
sharded over the device mesh and one *global* anti-entropy step is a join
all-reduce riding ICI:

* max-lattices (G/PN-Counter): ``jax.lax.pmax`` — literally one collective;
* arbitrary lattices (OR-Set, OpLog): recursive-doubling ``ppermute``
  exchange, log2(P) pairwise joins (the generic join all-reduce XLA has no
  primitive for);
* non-power-of-two meshes fall back to all_gather + tree reduction.

Multi-host scaling note: all of these are standard XLA collectives, so the
same jitted program spans hosts over DCN when `jax.distributed` initializes a
multi-host mesh — no reference-style NCCL/MPI translation layer exists or is
needed.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from crdt_tpu.ops import joins
from crdt_tpu.parallel import swarm as swarm_lib


def make_mesh(n_devices: int | None = None, axis: str = "replica") -> Mesh:
    """1-D mesh over the first n (default: all) local devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (axis,))


def shard_swarm(s: swarm_lib.Swarm, mesh: Mesh, axis: str = "replica") -> swarm_lib.Swarm:
    """Place a swarm with the replica axis sharded over the mesh."""
    sharding = NamedSharding(mesh, P(axis))
    return jax.device_put(s, sharding)


def allreduce_join(
    join_fn: Callable, x: Any, axis: str, axis_size: int, neutral: Any
) -> Any:
    """Generic join all-reduce inside shard_map: after this, every device
    holds the join of all devices' `x` (a single-instance state pytree).

    Power-of-two meshes use recursive doubling (XOR partner ppermute, log2(P)
    rounds — the classic all-reduce butterfly, here with an arbitrary lattice
    join instead of +).  Other sizes all_gather and tree-reduce locally.
    `neutral` must be the lattice's true join identity (e.g. oplog.empty —
    NOT zeros, which for sorted-log lattices is a real key and would inject
    phantom ops into the pad rows of the reduction).
    """
    if axis_size & (axis_size - 1) == 0:
        step = 1
        while step < axis_size:
            perm = [(i, i ^ step) for i in range(axis_size)]
            y = jax.tree.map(lambda l: jax.lax.ppermute(l, axis, perm), x)
            x = join_fn(x, y)
            step *= 2
        return x
    gathered = jax.tree.map(
        lambda l: jax.lax.all_gather(l, axis, axis=0), x
    )
    return joins.tree_reduce_join(jax.vmap(join_fn), gathered, neutral)


def sharded_converge(
    mesh: Mesh,
    join_batched: Callable,
    join_single: Callable,
    neutral: Any,
    axis: str = "replica",
) -> Callable:
    """Build a jitted global-convergence step over a sharded swarm:
    local tree-reduction within each device's replica shard, then a join
    all-reduce across the mesh, then broadcast back to all alive replicas.

    One call of the returned function ≡ the gossip fixpoint of the whole
    (possibly multi-host) swarm: the BASELINE "10K-replica all-reduce
    convergence" config.
    """
    axis_size = mesh.shape[axis]

    def local_step(state, alive):
        top_local = swarm_lib.alive_lub(state, alive, join_batched, neutral)
        top = allreduce_join(join_single, top_local, axis, axis_size, neutral)
        return swarm_lib.broadcast_where_alive(state, alive, top)

    shmapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
    )

    @jax.jit
    def step(s: swarm_lib.Swarm) -> swarm_lib.Swarm:
        return s.replace(state=shmapped(s.state, s.alive))

    return step


def pmax_converge(mesh: Mesh, axis: str = "replica") -> Callable:
    """Max-lattice fast path: global convergence of a counter swarm as a
    single fused pmax all-reduce over ICI — the TPU-native equivalent of one
    gossip round that converges everything at once (BASELINE.json)."""

    def local_step(state, alive):
        def leaf(x):
            m = alive.reshape((-1,) + (1,) * (x.ndim - 1))
            masked = jnp.where(m, x, jnp.zeros_like(x))
            top = jax.lax.pmax(masked.max(axis=0), axis)
            return jnp.where(m, jnp.broadcast_to(top[None], x.shape), x)

        return jax.tree.map(leaf, state)

    shmapped = shard_map(
        local_step, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(axis)
    )

    @jax.jit
    def step(s: swarm_lib.Swarm) -> swarm_lib.Swarm:
        return s.replace(state=shmapped(s.state, s.alive))

    return step
