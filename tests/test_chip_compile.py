"""Described-chip compiles: the kernels and jitted steps chip_smoke.py
dispatches, compiled for a v5e that is described, not attached (the TPU
compiler is installed; no chip is needed).  What Mosaic or XLA:TPU would
refuse on the chip — a misaligned slice, VMEM over budget, a program that
does not fit — fails here at no chip time.  A compile that passes is not
a chip run.

The topology is described inside module-scoped fixtures only (never at
import): one test worker loads the TPU library for this file, and every
worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from crdt_tpu.models import oplog
from crdt_tpu.ops import pallas_union as pu
from crdt_tpu.parallel import meshplane

LANES = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile can be written to the persistent cache but
    never read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _log_spec(sharding, shape):
    return oplog.OpLog(*(_spec(sharding, shape) for _ in range(6)),
                       _spec(sharding, shape, jnp.bool_))


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_fused_or_union_compiles(one_chip):
    c = 256
    plane = _spec(one_chip, (c, LANES))
    compiled = _compile(
        lambda ka, va, kb, vb: pu.sorted_union_columnar_fused(
            ka, va, kb, vb, out_size=c, interpret=False),
        plane, plane, plane, plane)
    assert "tpu_custom_call" in compiled.as_text()


def test_lex2_union_compiles(one_chip):
    c = 128
    p = _spec(one_chip, (c, LANES))
    compiled = _compile(
        lambda a, b: pu.sorted_union_columnar_fused_lex2(
            a[:2], a[2:], b[:2], b[2:], out_size=c, interpret=False),
        (p,) * 4, (p,) * 4)
    assert "tpu_custom_call" in compiled.as_text()


def test_lexn_union_compiles(one_chip):
    c = 64
    p = _spec(one_chip, (c, LANES))
    compiled = _compile(
        lambda a, b: pu.sorted_union_columnar_fused_lexn(
            a[:3], a[3:], b[:3], b[3:], out_size=c, interpret=False),
        (p,) * 5, (p,) * 5)
    assert "tpu_custom_call" in compiled.as_text()


def test_served_merge_compiles(one_chip):
    """The ingest merge every ReplicaNode drain dispatches, at a shard
    capacity of 2**16 rows (chip_smoke.py runs 2**19)."""
    compiled = _compile(oplog._merge_checked,
                        _log_spec(one_chip, (1 << 16,)),
                        _log_spec(one_chip, (1024,)))
    assert compiled.memory_analysis() is not None


def test_meshplane_vmap_step_compiles(one_chip):
    """The single-device mesh step: 4 shard lanes folded in one program."""
    plane = meshplane.MeshPlane(4, mode="on", engine="vmap")
    cap, b = 1 << 14, 512
    batch = _log_spec(one_chip, (4, b))
    compiled = plane._step_for(cap, b).lower(
        _log_spec(one_chip, (4, cap)),
        tuple(getattr(batch, c) for c in oplog.COLUMNS),
        _spec(one_chip, (4, b, 4), jnp.uint32)).compile()
    assert compiled.memory_analysis() is not None
