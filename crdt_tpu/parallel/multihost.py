"""Multi-host scaling: jax.distributed bootstrap + global-mesh anti-entropy.

The reference "scales" by adding loopback HTTP servers in one process
(/root/reference/main.go:316-323).  The TPU-native story has two rungs:

* **one pod slice** — crdt_tpu.parallel.mesh: collectives over ICI;
* **many hosts** — THIS module: the same jitted convergence program spans
  hosts over DCN once ``jax.distributed`` is initialized, because the
  collectives in mesh.py are ordinary XLA collectives — there is no
  NCCL/MPI-style translation layer to port (SURVEY.md §5 "Distributed
  communication backend").

Pattern (same code on every host):

    multihost.init_from_env()                  # JAX service bootstrap
    mesh = multihost.global_mesh()             # ALL devices, all hosts
    s = multihost.shard_host_local(local_rows, mesh)   # each host feeds
    step = mesh_lib.sharded_converge(mesh, ...)        # its own replicas
    s = step(s)                                # one global fixpoint

Host-level ingress (writes arriving at each host) stays on the
reference-wire HTTP runtime (crdt_tpu.api.net) — ops land in the host's
local replica rows between device steps.

Testing note: real multi-host needs real DCN; everything here degrades to
single-process (init_from_env returns False when no coordinator is
configured, global_mesh == local mesh), so the logic is exercised in CI on
the 8-device virtual CPU mesh and the driver's dryrun validates the
sharded program compiles + runs.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_from_env(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    autodetect: Optional[bool] = None,
) -> bool:
    """Initialize ``jax.distributed`` when a cluster is configured; no-op
    (returns False) otherwise.

    Three ways in:
    * explicit arguments;
    * the standard environment (JAX_COORDINATOR_ADDRESS /
      JAX_NUM_PROCESSES / JAX_PROCESS_ID);
    * ``autodetect=True`` (or env CRDT_TPU_MULTIHOST=1): call
      ``jax.distributed.initialize()`` with no arguments and let JAX's
      cluster detection find the TPU-pod/cluster runtime.  This must be an
      explicit opt-in — a bare laptop run cannot be distinguished from a
      pod host by absence of env vars alone.

    Safe to call twice (already-initialized returns True).  A FAILED
    bootstrap raises: silently proceeding single-host would let every host
    converge its own partition believing it is the global swarm.
    """
    if jax.distributed.is_initialized():
        return True
    if autodetect is None:
        autodetect = os.environ.get("CRDT_TPU_MULTIHOST") == "1"
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if (
        coordinator_address is None
        and os.environ.get("JAX_NUM_PROCESSES") is None
        and not autodetect
    ):
        return False  # single-process: nothing to do
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=(
            int(num_processes or os.environ["JAX_NUM_PROCESSES"])
            if (num_processes or os.environ.get("JAX_NUM_PROCESSES"))
            else None
        ),
        process_id=(
            int(process_id or os.environ["JAX_PROCESS_ID"])
            if (process_id or os.environ.get("JAX_PROCESS_ID"))
            else None
        ),
    )
    return True


def global_mesh(axis: str = "replica") -> Mesh:
    """1-D mesh over every device of every participating host (equals the
    local mesh in single-process runs)."""
    return Mesh(np.asarray(jax.devices()), (axis,))


def shard_host_local(host_local_state: Any, mesh: Mesh, axis: str = "replica") -> Any:
    """Build the GLOBAL swarm state from each host's local replica rows.

    Every host passes the rows it owns (leading axis = its local replica
    count); the result is one global array whose leading axis is the sum
    over hosts, sharded along ``axis``.  In single-process runs this is
    just ``device_put`` with the replica axis sharded.
    """
    sharding = NamedSharding(mesh, P(axis))
    if jax.process_count() == 1:
        return jax.device_put(host_local_state, sharding)
    return jax.tree.map(
        lambda l: jax.make_array_from_process_local_data(sharding, np.asarray(l)),
        host_local_state,
    )


def process_span() -> tuple[int, int]:
    """(process_id, process_count) — writer-id ranges for multi-host
    deployments come from this (ClusterConfig.rid_base = pid * per_host)."""
    return jax.process_index(), jax.process_count()
