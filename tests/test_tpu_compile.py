"""The Pallas kernels of the proof path compile for a TPU v5e chip.

Each kernel is lowered with ``interpret=False`` for one chip of a
described (not attached) v5e topology and compiled by the TPU compiler,
at the plane shapes of the widest geometry that verifies from bytes,
8 layers x 128 wide at batch 64: its merged opening is 2^22 elements,
32768 rows of 128 lanes.  A compile refuses what interpret mode cannot
see: unaligned slices, VMEM over budget, a kernel that cannot be
partitioned.  Nothing runs, so
this says nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
worker that runs this file keeps it until it exits.
"""
import os

import pytest

ROWS = (1 << 22) // 128          # plane rows of that merged opening


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe a chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _planes(sharding, *lead):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((*lead, ROWS, 128), jnp.uint32,
                                sharding=sharding)


def _tile(sharding):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((4, 1, 128), jnp.uint32, sharding=sharding)


def _fold_planes(s):
    from repro.field import FQ
    from repro.kernels.sumcheck_fold.kernel import fold_planes
    return fold_planes, (_planes(s, 4), _planes(s, 4), _tile(s)), \
        {"spec": FQ}


def _fold_halves_planes(s):
    from repro.field import FQ
    from repro.kernels.sumcheck_fold.kernel import fold_halves_planes
    return fold_halves_planes, (_planes(s, 4), _planes(s, 4), _tile(s),
                                _tile(s)), {"spec": FQ}


def _pow_mul_planes(s):
    from repro.field import FP
    from repro.kernels.sumcheck_fold.kernel import pow_mul_planes
    return pow_mul_planes, (_planes(s, 4), _planes(s, 4), _tile(s),
                            _tile(s)), {"spec": FP}


def _validity_tables_planes(s):
    from repro.field import FQ
    from repro.kernels.validity_tables.kernel import validity_tables_planes
    masks = tuple(_planes(s) for _ in range(6))
    return validity_tables_planes, masks + (
        _planes(s, 4), _planes(s, 4), _tile(s), _tile(s), _tile(s),
        _tile(s)), {"spec": FQ}


@pytest.mark.parametrize("kernel", [_fold_planes, _fold_halves_planes,
                                    _pow_mul_planes,
                                    _validity_tables_planes],
                         ids=lambda k: k.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args, statics = kernel(one_chip)
    compiled = fn.lower(*args, **statics, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
