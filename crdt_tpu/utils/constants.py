"""Shared constants for array-encoded CRDT state.

TPU-first dtype policy: everything device-side is int32.  Wall-clock
timestamps are stored as *millisecond offsets from a host-side epoch*
(`crdt_tpu.utils.clock.HostClock`) so they fit int32 (~24 days of range)
without enabling jax_enable_x64; uniqueness at TPU rates comes from the
(ts, replica_id, seq) triple, fixing the reference's same-millisecond
log-key collision (see SURVEY.md §0.1.2, /root/reference/main.go:187).
"""
import jax.numpy as jnp
import numpy as np

# Padding sentinel for sorted array-encoded sets/logs.  Real keys are
# strictly below it, so padded rows sort to the tail.  numpy scalars, NOT
# jnp: creating a jax array at import time would initialize the backend
# before the caller can pick a platform (tests pin the CPU; a process on
# a chip machine must not grab the chip merely by importing this).
SENTINEL = np.int32(2**31 - 1)
SENTINEL_PY = 2**31 - 1

# "No value yet" timestamp for LWW registers (all real ts are >= 0).
TS_NULL = np.int32(-1)

DEFAULT_DTYPE = jnp.int32
