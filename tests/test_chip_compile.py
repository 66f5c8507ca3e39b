"""Compile the Pallas kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax; it compiles for a topology that is
described, not attached, and refuses what the chip would refuse: VMEM
blocks that do not fit, tiles that do not align, programs larger than HBM.
Interpret mode on CPU can show none of that.  The aggregation cases
compile ``fl_aggregate``, through ``fl.state.masked_aggregate``, at the
paper MLP's per-leaf sizes (784·200, 200, 200·10, 10) for a row count R —
the population K on the dense engine, the participant bucket on the sparse
path, the micro-batch on the server — and assert the Pallas kernel is in
the compiled program; the selective-scan
cases compile its forward and backward kernels at Jamba2-3B's widths.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fl.state import masked_aggregate

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
    return masked_aggregate(g, d, w, d.shape[0], use_pallas=True)


def _subset(g, d, w):
    return masked_aggregate(g, d, w, jnp.int32(1000), use_pallas=True)


def _guarded(g, d, w):
    return masked_aggregate(g, d, w, 1, use_pallas=True)


# the divisor as the kernel's static denom (dense engine), traced and folded
# into the weights (sparse path, server), and 1 (weights that hold their own
# scaling, as a scheme round passes them; the case keeps the name it had
# when the kernel also sanitised non-finite deltas)
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


# --- the Mamba selective scan (kernels/selective_scan.py) ---------------------

SCAN_SHAPES = {"train": (10, 1), "eval": (1, 4)}  # (vmapped clients, batch)


@pytest.mark.parametrize("phase", sorted(SCAN_SHAPES))
def test_selective_scan_compiles_for_v5e(one_chip, phase):
    """Jamba2-3B's widths (S 1,024, d_inner 5,120, d_state 16): the LM
    cell's training step vmaps a batch of one over ten clients, its
    evaluation runs a batch of four.  Both kernels are in the program, and
    the tile-order views of the [B, S, d_inner] operands are bitcasts: the
    forward needs no temporary (a relayout would take 210 MB)."""
    import importlib
    K = importlib.import_module("repro.kernels.selective_scan")
    clients, B = SCAN_SHAPES[phase]
    S, di, N = 1024, 5120, 16
    f32 = jnp.float32

    def shape(*s):
        return jax.ShapeDtypeStruct(s, f32, sharding=one_chip)

    args = (shape(clients, B, S, di), shape(clients, B, S, di),
            shape(clients, B, S, N), shape(clients, B, S, N),
            shape(di, N), shape(di))

    def scan(x, dt, b, c, A, D):
        return jax.vmap(lambda *a: K.selective_scan(*a, A, D))(x, dt, b, c)

    def loss(*a):
        y, h = scan(*a)
        return jnp.sum(y) + jnp.sum(h)

    fwd = jax.jit(scan).lower(*args).compile()
    assert "mamba_scan_fwd" in fwd.as_text()
    assert fwd.memory_analysis().temp_size_in_bytes == 0
    grad = jax.jit(jax.grad(loss, argnums=range(6))).lower(*args).compile()
    text = grad.as_text()
    assert "mamba_scan_fwd" in text and "mamba_scan_bwd" in text
