"""Compile the aggregation kernel for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax; it compiles for a topology that is
described, not attached, and refuses what the chip would refuse: VMEM
blocks that do not fit, tiles that do not align, programs larger than HBM.
Interpret mode on CPU can show none of that.  Every case compiles the
kernel at the paper MLP's per-leaf sizes (784·200, 200, 200·10, 10) for a
row count R — the population K on the dense engine, the participant bucket
on the sparse path, the micro-batch on the server — and asserts the Pallas
kernel is in the compiled program.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

LEAF_SIZES = (784 * 200, 200, 200 * 10, 10)
ROWS = (10, 64, 256, 1024, 10_000)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _plain(g, d, w):
    return ops.fl_aggregate(g, d, w, use_pallas=True)


def _subset(g, d, w):
    return ops.fl_aggregate_subset(g, d, w, jnp.int32(1000), use_pallas=True)


def _guarded(g, d, w):
    return ops.fl_aggregate_guarded(g, d, w, use_pallas=True)


VARIANTS = {"plain": _plain, "subset": _subset, "guarded": _guarded}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("M", LEAF_SIZES)
@pytest.mark.parametrize("R", ROWS)
def test_fl_aggregate_compiles_for_v5e(one_chip, R, M, variant):
    g = jax.ShapeDtypeStruct((M,), jnp.float32, sharding=one_chip)
    d = jax.ShapeDtypeStruct((R, M), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((R,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(VARIANTS[variant]).lower(g, d, w).compile()
    assert "tpu_custom_call" in compiled.as_text()
