"""Compile the ``soa-device`` programs for a described TPU v5e at real widths.

Nothing runs here: the chip's compiler, installed with libtpu, compiles for
a chip that is described and not attached, and refuses what it would
refuse on the chip.  The topology is described inside a module fixture and
never while a module is imported, because only one process at a time may
load libtpu; all such tests stay in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import lsh_hash as lh
from repro.kernels import ops

T = 10  # the paper's t


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip cannot be read back from
        # the persistent cache without one, so keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# the engine pads a batch of 1,000 to 1,024 rows; d=10 is the paper's
# synthetic set, d=54 its widest Table 1 set (covertype)
@pytest.mark.parametrize("d", [10, 54])
def test_lsh_hash_compiles_to_a_mosaic_kernel(one_chip, d):
    compiled = lh.lsh_hash.lower(
        _shape(one_chip, (1024, d), jnp.float32),
        _shape(one_chip, (T,), jnp.float32),
        _shape(one_chip, (2, T, d), jnp.int32),
        inv_cell=1 / 1.5,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (1024, T, 2)


# the engine calls them in a batch's local slot space: a 1,000-point batch
# of t=10 pads to 1,024 rows and 2^14 slots.  Larger slot vectors compile
# too, up to 2^21, the most buckets the paper stream (200k points x 10
# tables) can create
@pytest.mark.parametrize("cap", [2**14, 2**17, 2**21])
def test_occupancy_and_support_programs_compile(one_chip, cap):
    slots = _shape(one_chip, (1024, T), jnp.int32)
    counts = ops.slot_counts.lower(slots, n_slots=cap).compile()
    assert counts.out_info.shape == (cap,)
    stats = ops.bucket_core_stats.lower(
        slots, _shape(one_chip, (cap,), jnp.int32), k=10).compile()
    assert [o.shape for o in stats.out_info] == [(1024,), (1024,)]


# the connectivity epoch over the paper's 200k-point window (row capacity
# 2^18): the blobs' dense buckets fill a slot capacity of 2^14, sparse
# buckets can take it to 2^21
@pytest.mark.parametrize("n_slots", [2**14, 2**21])
def test_core_components_program_compiles(one_chip, n_slots):
    rows = 2**18
    compiled = ops.core_components.lower(
        _shape(one_chip, (rows * T,), jnp.int32),
        _shape(one_chip, (rows,), jnp.bool_),
        n_slots=n_slots,
    ).compile()
    least, rounds = compiled.out_info
    assert least.shape == (n_slots,) and least.dtype == jnp.int32
    assert rounds.shape == () and rounds.dtype == jnp.int32
