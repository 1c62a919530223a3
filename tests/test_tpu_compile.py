"""Compile the chip's hot path for a described TPU v5e, without the chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: it refuses what the chip would refuse (unaligned
slices, VMEM overuse, unlowerable kernels). The Pallas kernels compile at
4M rows and the fused scan step from `__graft_entry__.entry()` at a
1M-row batch, for one chip of a `v5e:2x2` topology, with x64 off as on
the chip. Nothing runs, so these say nothing about results or times.

The topology is described only inside the module fixture: one process at
a time may load the TPU library, and a description made while modules are
imported would make the test workers collect different tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deequ_tpu.ops import pallas_kernels

KERNEL_ROWS = 1 << 22
STEP_ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """Entries compiled for a described chip cannot be read back without
    one, so these compiles never touch the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize(
    "kernel, dtypes",
    [
        ("hll_register_max", (jnp.int32,)),
        ("hist16", (jnp.int32,)),
        ("masked_moments", (jnp.float32, jnp.float32)),
        ("masked_centered_sumsq", (jnp.float32, jnp.float32, None)),
    ],
)
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kernel, dtypes):
    args = [
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        if dt is None
        else jax.ShapeDtypeStruct((KERNEL_ROWS,), dt, sharding=one_chip)
        for dt in dtypes
    ]
    assert _compile(getattr(pallas_kernels, kernel), *args) == 1


def test_fused_scan_step_compiles_for_v5e(one_chip, no_persistent_cache, monkeypatch):
    """The flagship step with the Pallas kernels in it: this process sees
    a CPU, so the kernels' TPU gate is opened here, in the test."""
    import __graft_entry__

    monkeypatch.setattr(pallas_kernels, "_USABLE", True)
    fn, (inputs,) = __graft_entry__.entry()
    wire = {np.dtype(np.float64): jnp.float32, np.dtype(np.int64): jnp.int32}
    shapes = {
        key: jax.ShapeDtypeStruct(
            (STEP_ROWS,), wire.get(np.asarray(a).dtype, np.asarray(a).dtype),
            sharding=one_chip,
        )
        for key, a in inputs.items()
    }
    # HLL register max, plus moments and centered sum of squares
    assert _compile(fn, shapes) >= 3


def test_mesh_quantile_step_compiles_for_v5e(topo, no_persistent_cache, monkeypatch):
    """The four-chip mesh step of a quantile and a mean at 2^21 rows per
    chip: every shard runs the hist16 kernel (and the moment fold) inside
    `shard_map`, and no device sort is left in the program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deequ_tpu.analyzers import ApproxQuantile, Mean
    from deequ_tpu.parallel.distributed import DATA_AXIS, _get_distributed_fn

    monkeypatch.setattr(pallas_kernels, "_USABLE", True)
    mesh = Mesh(np.array(topo.devices[:4]), (DATA_AXIS,))
    sharded = NamedSharding(mesh, P(DATA_AXIS))
    quantile, mean = ApproxQuantile("x", 0.5), Mean("x")
    keys = {s.key for a in (quantile, mean) for s in a.input_specs()}
    shapes = {
        key: jax.ShapeDtypeStruct(
            (4 << 21,), jnp.float32 if key.startswith("num:") else jnp.bool_,
            sharding=sharded,
        )
        for key in keys
    }
    fn = _get_distributed_fn([mean], mesh, DATA_AXIS, assisted=[quantile])
    hlo = fn.lower(shapes).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2  # hist16 and the moment fold
    assert "hist16" in hlo
    assert not re.search(r"\bsort\(", hlo)
