"""The replica-sharded merge plane: ALL keyspace shards fold in ONE step.

The keyspace tier (crdt_tpu.keyspace) carved the host plane into S
independent `ReplicaNode` shards — but each shard still merged with its
own host-driven dispatch, so a fleet pull round cost S device round
trips.  This module lays the S shard op-logs out on a device `Mesh`
axis and compiles ONE fused LUB step that converges every lane at once:
stack the lanes, sort each lane's ingest batch, run the checked
sorted-union merge under `jax.vmap`, unstack — all inside a single
compiled program, so `merge_dispatches` ticks ONCE per mesh step
regardless of S.

Engine selection (what the compiled step is wrapped in):

* ``pjit``      — `jax.jit` with the lane axis pinned to the mesh via
                  `with_sharding_constraint(NamedSharding(mesh,
                  P(axis)))`; XLA partitions the vmapped fold across
                  devices (GSPMD).  Chosen when >= 2 devices divide the
                  lane count.
* ``vmap``      — single-device fusion: still ONE dispatch for all S
                  lanes, no cross-device partitioning.  What one chip
                  (or CPU CI without emulated host devices) runs.

Bit-parity: each lane's fold is `oplog._merge_checked` of its
host-sorted, SENTINEL-padded batch (`oplog.from_host_ops`) — exactly the
host path's `_merge_batch`.  `tests/test_meshplane.py` pins per-shard
state/vv bit-equality mesh-vs-host on randomized traces;
`benches/bench_keyspace.py --mesh` re-asserts it inside the timing loop.

The plane operates on `PendingMerge` handles (api.node): each lane's
host bookkeeping (accept, dedup, indexes, vv) already happened under
that node's lock, which stays HELD across the fused step so commit
rebinds the merged log race-free.  If the fused step itself fails, every
lane lands with its own inline host dispatch (`commit_inline`) — a lane
is never left with host indexes ahead of its log — and the failure is
made loud: ``meshplane_fallbacks`` ticks, a ``meshplane_fallback`` event
carrying the error text lands in the node's events, and an
`EngineFallback` warning is raised (an error under
``warnings.simplefilter("error", EngineFallback)``, as chip_smoke.py runs).
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from crdt_tpu.models import oplog
from crdt_tpu.obs.trace import region
from crdt_tpu.ops import union_engine
from crdt_tpu.parallel.mesh import make_mesh
from crdt_tpu.utils.metrics import Metrics

MESH_MODES = ("auto", "on", "off")

# a zero-fresh lane's ingest batch: pure padding (an identity fold)
_NO_OPS = {name: np.zeros(0, bool if name == "is_num" else np.int32)
           for name in oplog.COLUMNS}


def _mesh_divisor(n_lanes: int, n_devices: int) -> int:
    """Largest device count d <= min(n_lanes, n_devices) with d | n_lanes
    (the pjit sharding constraint needs the lane axis to split evenly
    across the mesh)."""
    for d in range(min(n_lanes, n_devices), 0, -1):
        if n_lanes % d == 0:
            return d
    return 1


def select_engine(n_lanes: int, mode: str = "auto") -> Optional[str]:
    """Pick the fused engine for ``n_lanes`` shard lanes, or None for the
    per-lane host path.  ``auto`` fuses only when fusion can actually win
    (>= 2 devices to spread over and >= 2 lanes to fuse); ``on`` always
    fuses (single device degrades to the vmap engine — still one
    dispatch for all lanes); ``off`` never does."""
    if mode not in MESH_MODES:
        raise ValueError(
            f"keyspace_mesh={mode!r}: must be one of {'|'.join(MESH_MODES)}")
    if mode == "off" or n_lanes < 1:
        return None
    n_dev = len(jax.devices())
    if mode == "auto" and (n_dev < 2 or n_lanes < 2):
        return None
    return "pjit" if _mesh_divisor(n_lanes, n_dev) >= 2 else "vmap"


def _lane_fold(log: oplog.OpLog, batch_cols: Tuple[jax.Array, ...]):
    """One lane: the checked merge of its host-sorted, padded ingest batch
    (== the host path's from_host_ops + merge_checked).  Traced under
    vmap — the whole mesh step is this, S times, in one program."""
    return oplog._merge_checked(log, oplog.OpLog(*batch_cols))


class MeshPlane:
    """The fused cross-shard merge engine for one `ShardedKeyspace`.

    Step functions are compiled once per (lane capacity, batch capacity)
    pair — both are rounded to powers of two by the caller/the keyspace
    growth rule, so recompiles are O(log n), never per-step (the
    CRDT002 jit-in-a-loop rule the linter enforces).
    """

    def __init__(
        self,
        n_lanes: int,
        *,
        mode: str = "auto",
        metrics: Optional[Metrics] = None,
        axis: str = "shard",
        engine: Optional[str] = None,
    ):
        self.n_lanes = n_lanes
        self.mode = mode
        self.axis = axis
        self.metrics = metrics if metrics is not None else Metrics()
        # the engine override pins a specific engine (tests exercise the
        # single-device vmap path explicitly on a multi-device host)
        self.engine = engine if engine is not None \
            else select_engine(n_lanes, mode)
        if self.engine not in ("pjit", "vmap"):
            raise ValueError(f"unknown mesh engine {self.engine!r}")
        self.mesh = None
        self.sharding = None
        self.n_devices = 1
        if self.engine == "pjit":
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.n_devices = _mesh_divisor(n_lanes, len(jax.devices()))
            self.mesh = make_mesh(self.n_devices, axis=axis)
            self.sharding = NamedSharding(self.mesh, P(axis))
        # devices the last step's stacked lanes were placed on (the
        # multi-chip check reads it: one lane per chip, not all on 0)
        self.last_devices: Tuple[int, ...] = ()
        self._steps: Dict[Tuple[int, int], Callable] = {}

    # ---- compiled step construction ----

    def _build_step(self, capacity: int, batch_cap: int) -> Callable:
        n = self.n_lanes
        vfold = jax.vmap(_lane_fold)

        if self.engine == "pjit":
            sharding = self.sharding

            def run(logs, cols):
                logs = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, sharding),
                    logs)
                cols = tuple(
                    jax.lax.with_sharding_constraint(c, sharding)
                    for c in cols)
                return vfold(logs, cols)

        else:  # vmap: single-device fusion
            run = vfold

        def step(logs, cols, digs):
            merged, n_unique = run(logs, cols)
            # audit-digest fold riding the SAME dispatch (crdt_tpu.obs
            # .audit): per-lane sum of the batch's digest rows mod 2**32.
            # Padding rows carry all-zero lanes (additive identity), so
            # no mask tensor is needed; commit() bit-compares this
            # against the host-side sum (mesh-vs-host digest parity).
            dig_sum = jnp.sum(digs, axis=1, dtype=jnp.uint32)
            # unstack INSIDE the program: the caller gets S per-lane logs
            # from the one compiled call, no per-lane slice dispatches
            lanes = [jax.tree.map(lambda x, i=i: x[i], merged)
                     for i in range(n)]
            return lanes, n_unique, dig_sum

        return jax.jit(step)

    def _step_for(self, capacity: int, batch_cap: int) -> Callable:
        key = (capacity, batch_cap)
        fn = self._steps.get(key)
        if fn is None:
            fn = self._steps[key] = self._build_step(capacity, batch_cap)
        return fn

    # ---- the fused converge ----

    def converge(self, pendings: List[Any]) -> int:
        """Fold every pending lane in ONE device dispatch and commit.

        ``pendings`` are `PendingMerge` handles whose node locks are HELD
        (merge_begin / add_commands_begin); all are released on return,
        success or failure.  Returns total absorbed (fresh + adopted)
        across lanes.  Zero-fresh lanes ride along as identity folds so
        the compiled shape stays static across steps.
        """
        if not pendings:
            return 0
        if len(pendings) != self.n_lanes:
            for p in pendings:
                p.abort()
            raise ValueError(
                f"mesh plane built for {self.n_lanes} lanes, "
                f"got {len(pendings)} pendings")
        if not any(p.fresh for p in pendings):
            # nothing anywhere: skip the device entirely (the host
            # path's no-op round does the same)
            return land_all_inline(pendings)
        try:
            # uniform lane capacity: vmap stacks to [S, L], so every lane
            # grows (tail padding, lossless) to the max needed, rounded to
            # a power of two to bound recompiles
            need = max(p.rows_held() + p.fresh for p in pendings)
            cap = max(p.node.log.capacity for p in pendings)
            while cap < need:
                cap *= 2
            for p in pendings:
                if p.node.log.capacity < cap:
                    p.node.log = oplog.grow(p.node.log, cap)
                    p.node.metrics.inc("log_grow")

            batch_cap = oplog.batch_capacity(max(p.fresh for p in pendings))

            logs = jax.tree.map(
                lambda *xs: jnp.stack(xs), *[p.node.log for p in pendings])
            batches = [oplog.from_host_ops(batch_cap, p.ops or _NO_OPS)
                       for p in pendings]
            cols = tuple(np.stack([getattr(b, name) for b in batches])
                         for name in oplog.COLUMNS)
            digs = np.stack([_pad_dig(p.dig, batch_cap) for p in pendings])
            if self.sharding is not None:
                # place each lane on its own device before the step, so
                # the program's inputs are already split over the mesh
                logs, cols = jax.device_put((logs, cols), self.sharding)
            self.last_devices = tuple(sorted(
                d.id for d in logs.ts.sharding.device_set))

            step = self._step_for(cap, batch_cap)
            with self.metrics.timer("merge"):
                lanes, n_unique, dig_sum = step(logs, cols, digs)
                # ONE host sync for all lanes' counts AND digest sums
                with region("crdt.wait.device"):
                    n_host, dig_host = jax.device_get((n_unique, dig_sum))
        except Exception as exc:
            # engine failure: land every lane with its own inline host
            # dispatch so no lane is left with indexes ahead of its log,
            # then make the failure loud — a fused step that fails on a
            # chip must never look like one that ran
            self.metrics.inc("meshplane_fallbacks")
            total = land_all_inline(pendings)
            error = f"{type(exc).__name__}: {exc}"
            pendings[0].node.events.emit(
                "meshplane_fallback", engine=self.engine, error=error)
            from crdt_tpu.models.oplog_engine import EngineFallback

            warnings.warn(
                f"mesh plane {self.engine} step failed, lanes landed "
                f"inline: {error}", EngineFallback, stacklevel=2)
            return total
        # one fused device dispatch for ALL lanes — the counter the
        # one-dispatch-per-step assertions pin; per-lane attribution comes
        # from each node's _count_lane_fold (merge_dispatches{shard=i})
        self.metrics.inc("merge_dispatches")
        union_engine.record_union_path(
            "sort", registry=self.metrics.registry)
        total = 0
        first_exc: Optional[BaseException] = None
        for i, p in enumerate(pendings):
            try:
                total += p.commit(
                    lanes[i], int(n_host[i]),
                    digest=dig_host[i] if p.dig_sum is not None else None)
            except BaseException as exc:
                # commit's finally released THIS lane's lock; keep
                # committing the siblings so none of their locks leak,
                # then surface the first failure
                if first_exc is None:
                    first_exc = exc
        if first_exc is not None:
            raise first_exc
        return total


def land_all_inline(pendings: List[Any]) -> int:
    """Commit every still-open pending with its own inline host dispatch.

    Keeps draining after a lane's ``commit_inline`` raises (its finally
    already released that lane's lock) so NO lane's node lock leaks, then
    re-raises the first failure."""
    total = 0
    first_exc: Optional[BaseException] = None
    for p in pendings:
        if p.done:
            continue
        try:
            total += p.commit_inline()
        except BaseException as exc:
            if first_exc is None:
                first_exc = exc
    if first_exc is not None:
        raise first_exc
    return total


def _pad_dig(dig: Optional[np.ndarray], cap: int) -> np.ndarray:
    """One lane's audit-digest rows zero-padded to ``cap`` (zeros are the
    lane sum's additive identity — see crdt_tpu.ops.digest.lane_sum).
    A lane with the audit plane off contributes all-zeros; its commit is
    then called with digest=None so no spurious parity check runs."""
    out = np.zeros((cap, 4), np.uint32)
    if dig is not None and len(dig):
        out[:len(dig)] = dig
    return out
